import os
# 512 virtual host devices, on the CPU by design: this probe never holds
# an accelerator, even on a machine that has one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Subprocess helper: compile merge schedules / train steps on the
production meshes and print collective byte accounting as JSON.
(Separate process because jax locks the device count at first init —
benchmarks.run itself stays on its own devices.)
"""

import argparse  # noqa: E402
import json      # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import merge as merge_lib              # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo       # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402


def merge_bytes(schedule: str, payload_mb: float, n_pod: int = 2):
    """DCN/ICI bytes of one merge of a dense tower of the given size."""
    mesh = make_production_mesh(multi_pod=True)
    n = int(payload_mb * 1e6 / 4)
    x = jax.ShapeDtypeStruct((n_pod, n), jnp.float32)
    sh = NamedSharding(mesh, P("pod", None))

    if schedule == "flat":
        fn = lambda v: merge_lib.flat_mean({"w": v})
    elif schedule == "two_phase":
        fn = lambda v: merge_lib.two_phase_mean({"w": v}, mesh)
    elif schedule == "bf16":
        fn = lambda v: merge_lib.two_phase_mean({"w": v}, mesh, payload_dtype=jnp.bfloat16)
    elif schedule == "int8_ef":
        fn = lambda v: merge_lib.int8_ef_mean(
            {"w": v}, {"w": jnp.zeros((n_pod, n), jnp.float32)}, mesh)[0]
    else:
        raise ValueError(schedule)
    compiled = jax.jit(fn, in_shardings=(sh,)).lower(x).compile()
    res = analyze_hlo(compiled.as_text(), devices_per_pod=256)
    c = res["collectives"]
    return {"schedule": schedule, "payload_mb": payload_mb,
            "dcn_bytes_per_device": c.dcn_bytes,
            "ici_bytes_per_device": c.ici_bytes,
            "total_bytes_per_device": c.total_bytes}


def sparse_bytes(placement: str, rows: int = 1 << 18, dim: int = 64,
                 capacity: int = 1 << 13):
    """Per-step collective bytes of one working-set pull+push on the
    production multi-pod mesh: ``routed`` (explicit all_to_all request
    routing, ``repro.core.routed_embedding``) vs ``gather`` (GSPMD
    partitions the gather/scatter over the row-sharded table into masked
    partials + value-blind all-reduce).

    Both probes take the already-deduplicated uid stream as input — dedup
    cost is placement-independent, so the accounting isolates the wire the
    --placement flag actually changes."""
    from repro.core import routed_embedding as routed
    from repro.core.sparse_optim import SparseAdagrad, SparseAdagradConfig

    mesh = make_production_mesh(multi_pod=True)
    axes = ("pod", "data", "model")
    n_shards = 2 * 16 * 16
    opt = SparseAdagrad(SparseAdagradConfig(lr=0.1))
    table_sh = NamedSharding(mesh, P(axes, None))
    if placement == "routed":
        cap_local = capacity // n_shards
        pull_fn, push_fn = routed.make_routed_pull_push(
            mesh, rows // n_shards, dim, cap_local, cap_local,
            shard_axes=axes,
        )
        ids_sh = NamedSharding(mesh, P(axes))   # each shard owns its uids

        def step(table, accum, uids):
            pulled, _, _ = pull_fn(table, uids)
            # row update derived from the pulled rows: nothing constant-folds
            new_table, new_accum, _ = push_fn(
                table, accum, uids, pulled * 0.01, opt.cfg.lr, opt.cfg.eps
            )
            return new_table, new_accum

    elif placement == "gather":
        ids_sh = NamedSharding(mesh, P())       # global replicated requests

        def step(table, accum, uids):
            pulled = jnp.take(table, uids, axis=0)
            return opt.apply_rows(table, accum, uids, pulled * 0.01)

    else:
        raise ValueError(placement)

    shapes = (
        jax.ShapeDtypeStruct((rows, dim), jnp.float32),
        jax.ShapeDtypeStruct((rows, dim), jnp.float32),
        jax.ShapeDtypeStruct((capacity,), jnp.int32),
    )
    compiled = (
        jax.jit(step, in_shardings=(table_sh, table_sh, ids_sh))
        .lower(*shapes)
        .compile()
    )
    res = analyze_hlo(compiled.as_text(), devices_per_pod=256)
    c = res["collectives"]
    return {"placement": placement, "rows": rows, "dim": dim,
            "capacity": capacity,
            "dcn_bytes_per_device": c.dcn_bytes,
            "ici_bytes_per_device": c.ici_bytes,
            "total_bytes_per_device": c.total_bytes}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", required=True, choices=["merge", "sparse"])
    ap.add_argument("--schedule", default="flat")
    ap.add_argument("--placement", default="routed")
    ap.add_argument("--payload-mb", type=float, default=64.0)
    args = ap.parse_args()
    if args.probe == "merge":
        print(json.dumps(merge_bytes(args.schedule, args.payload_mb)))
    elif args.probe == "sparse":
        print(json.dumps(sparse_bytes(args.placement)))


if __name__ == "__main__":
    main()
