import os
# compiled on 512 virtual host devices, on the CPU by design: the dry run
# never holds an accelerator, even on a machine that has one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production meshes and record memory / cost / collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch qwen3-14b
    PYTHONPATH=src python -m repro.launch.dryrun --mesh both            # all cells

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json; the roofline
(benchmarks/roofline.py) and EXPERIMENTS.md read from there.

NOTE: the JAX_PLATFORMS / XLA_FLAGS lines above MUST execute before any
other import (jax locks the platform and device count on first init) — do
not move them.
"""

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402

from repro.configs import get as get_arch, list_archs  # noqa: E402
from repro.core.kstep import KStepConfig               # noqa: E402
from repro.launch import cells as cells_lib            # noqa: E402
from repro.launch.hlo_analysis import (                # noqa: E402
    analyze_hlo,
    cost_analysis_dict,
    memory_analysis_dict,
)
from repro.launch.mesh import make_production_mesh     # noqa: E402
from repro.sharding.specs import named_shardings       # noqa: E402


def run_step(step, mesh, devices_per_pod: int, verbose: bool = True):
    in_shardings = tuple(
        named_shardings(s, mesh) for s in step.in_specs
    )
    t0 = time.perf_counter()
    jitted = jax.jit(step.fn, in_shardings=in_shardings, donate_argnums=step.donate)
    lowered = jitted.lower(*step.args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    mem = memory_analysis_dict(compiled)
    cost = cost_analysis_dict(compiled)
    # Loop-aware analysis: XLA cost_analysis counts while bodies once; the
    # HLO analyzer applies known_trip_count multiplicities (see hlo_analysis).
    hlo = analyze_hlo(compiled.as_text(), devices_per_pod)
    coll = hlo["collectives"]
    if verbose:
        print(f"    {step.name}: lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"flops/dev={hlo['flops']:.3e} bytes/dev={hlo['bytes_accessed']:.3e} "
              f"coll={coll.total_bytes/1e6:.1f}MB/dev (dcn {coll.dcn_bytes/1e6:.2f}MB)")
        print(f"      memory_analysis: {mem}")
    return {
        "name": step.name,
        "weight": step.weight,
        "model_flops": step.model_flops,
        "lower_seconds": t_lower,
        "compile_seconds": t_compile,
        "memory": mem,
        "cost": cost,
        "hlo": {"flops": hlo["flops"], "bytes_accessed": hlo["bytes_accessed"],
                "loop_corrected_computations": hlo["n_while_corrected"]},
        "collectives": {
            "total_bytes_per_device": coll.total_bytes,
            "ici_bytes_per_device": coll.ici_bytes,
            "dcn_bytes_per_device": coll.dcn_bytes,
            "by_kind": coll.by_kind(),
            "n_ops": len(coll.per_op),
        },
    }


def run_cell(arch_name, shape_name, mesh_name, k: int, merge: str,
             out_dir: str, smoke: bool = False, verbose: bool = True,
             lm_style: str = "tp_fsdp", gin_style: str = "sharded_nodes",
             recsys_style: str = "global_dedup"):
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    devices_per_pod = 256 if mesh_name == "multi" else 0
    kcfg = KStepConfig(k=k, merge=merge)
    cell = cells_lib.build_cell(arch_name, shape_name, mesh, kcfg, smoke=smoke,
                                lm_style=lm_style, gin_style=gin_style,
                                recsys_style=recsys_style)
    rec = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "k": k, "merge": merge, "kind": cell.kind, "lm_style": lm_style,
        "gin_style": gin_style,
        "n_devices": mesh.size, "steps": {}, "skip": cell.skip,
    }
    if cell.skip:
        if verbose:
            print(f"  SKIP {arch_name} x {shape_name}: {cell.skip}")
    else:
        for name, step in cell.steps.items():
            rec["steps"][name] = run_step(step, mesh, devices_per_pod, verbose)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch_name}__{shape_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--merge", default="two_phase",
                    choices=["flat", "two_phase", "bf16", "int8_ef"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for the output dir")
    ap.add_argument("--lm-style", default="tp_fsdp",
                    choices=["tp_fsdp", "fsdp_seq"])
    ap.add_argument("--gin-style", default="sharded_nodes",
                    choices=["sharded_nodes", "replicated_nodes", "sharded_bf16"])
    ap.add_argument("--recsys-style", default="global_dedup",
                    choices=["global_dedup", "local_dedup", "routed"])
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = []
    for mesh_name in meshes:
        for a in archs:
            spec = get_arch(a)
            shapes = list(spec.shapes) if args.shape == "all" else [args.shape]
            for s in shapes:
                print(f"[{mesh_name}] {a} x {s}")
                out_dir = os.path.join(args.out + args.tag, mesh_name)
                try:
                    run_cell(a, s, mesh_name, args.k, args.merge, out_dir,
                             smoke=args.smoke, lm_style=args.lm_style,
                             gin_style=args.gin_style,
                             recsys_style=args.recsys_style)
                except Exception:
                    traceback.print_exc()
                    failures.append((mesh_name, a, s))
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete.")


if __name__ == "__main__":
    main()
