"""Roofline derivation from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell, from ``experiments/dryrun``:

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s        (197e12 bf16)
    memory term     = HLO_bytes_per_device / HBM_bw             (819e9 B/s)
    collective term = ICI bytes / ICI_bw + DCN bytes / DCN_bw   (50e9 / 2.5e9)

FLOPs and bytes come from ``compiled.cost_analysis()`` of the partitioned
(per-device) module; collective bytes from the HLO wire model in
launch/hlo_analysis.py.  Train cells combine their two executables as
``local*(k-1)/k + merge/k`` (the k-step amortization).

Caveats (documented in EXPERIMENTS.md): the CPU backend promotes bf16 dots
to f32, so 'bytes accessed' is an upper bound (~2x) for bf16-dominated
models; DCN bandwidth is an assumption (the spec sheet gives ICI only).

``--measure`` adds the MEASURED referee for the fused sparse kernels: a
sparse hot-path micro-benchmark (pull -> bag fwd/bwd -> push, the exact
backend/engine code the trainer runs) per placement x {fused, unfused},
reporting steps/sec, ``cost_analysis`` bytes-accessed/FLOPs, and HLO op
counts of the compiled step, emitted to ``BENCH_roofline.json`` so every
later PR diffs fusion wins (and regressions) as numbers.  Each cell also
records ``kernel_mode`` — on this CPU container fused ops execute through
the jnp reference (or interpret under REPRO_KERNEL_INTERPRET=1), so the
*measured* fused-vs-unfused delta is only meaningful on a real TPU; the
``model_bytes`` field carries the analytic per-step HBM-traffic model
(intermediates each path materializes), which is backend-independent.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
DCN_BW = 2.5e9   # assumed per-chip inter-pod bandwidth


def model_flops_note(rec: Dict) -> float:
    steps = rec.get("steps", {})
    for s in steps.values():
        return s.get("model_flops", 0.0)
    return 0.0


def cell_terms(rec: Dict) -> Optional[Dict]:
    steps = rec.get("steps", {})
    if not steps:
        return None
    n_dev = rec.get("n_devices", 256)
    agg = {"compute_s": 0.0, "memory_s": 0.0, "ici_s": 0.0, "dcn_s": 0.0,
           "flops_dev": 0.0, "bytes_dev": 0.0, "coll_ici": 0.0, "coll_dcn": 0.0,
           "model_flops": 0.0}
    for s in steps.values():
        w = s.get("weight", 1.0)
        # loop-aware analyzer numbers (fall back to XLA cost_analysis)
        hlo = s.get("hlo", {})
        flops = hlo.get("flops") or s.get("cost", {}).get("flops", 0.0) or 0.0
        bytes_acc = (hlo.get("bytes_accessed")
                     or s.get("cost", {}).get("bytes accessed", 0.0) or 0.0)
        ici = s.get("collectives", {}).get("ici_bytes_per_device", 0)
        dcn = s.get("collectives", {}).get("dcn_bytes_per_device", 0)
        agg["flops_dev"] += w * flops
        agg["bytes_dev"] += w * bytes_acc
        agg["coll_ici"] += w * ici
        agg["coll_dcn"] += w * dcn
        agg["model_flops"] += w * s.get("model_flops", 0.0)
    agg["compute_s"] = agg["flops_dev"] / PEAK_FLOPS
    agg["memory_s"] = agg["bytes_dev"] / HBM_BW
    agg["ici_s"] = agg["coll_ici"] / ICI_BW
    agg["dcn_s"] = agg["coll_dcn"] / DCN_BW
    agg["collective_s"] = agg["ici_s"] + agg["dcn_s"]
    terms = {"compute": agg["compute_s"], "memory": agg["memory_s"],
             "collective": agg["collective_s"]}
    agg["dominant"] = max(terms, key=terms.get)
    bound = max(terms.values())
    agg["bound_s"] = bound
    # useful fraction: model FLOPs per device vs what the bottleneck allows
    agg["useful_flops_dev"] = agg["model_flops"] / n_dev
    agg["flops_ratio"] = (
        agg["useful_flops_dev"] / agg["flops_dev"] if agg["flops_dev"] else 0.0
    )
    agg["roofline_fraction"] = (
        (agg["useful_flops_dev"] / PEAK_FLOPS) / bound if bound > 0 else 0.0
    )
    return agg


def load_records(base: str = "experiments/dryrun") -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(base, "*", "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        rec["_path"] = path
        out.append(rec)
    return out


def table(base: str = "experiments/dryrun", mesh: Optional[str] = None) -> List[Dict]:
    rows = []
    for rec in load_records(base):
        if mesh and rec.get("mesh") != mesh:
            continue
        t = cell_terms(rec)
        row = {"arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
               "kind": rec.get("kind"), "skip": rec.get("skip")}
        if t:
            row.update(t)
        rows.append(row)
    return rows


def print_table(base: str = "experiments/dryrun", mesh: str = "single"):
    hdr = ("arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
           "model/HLO_flops,roofline_fraction")
    print(hdr)
    for r in sorted(table(base, mesh), key=lambda r: (r["arch"], r["shape"])):
        if r.get("skip"):
            print(f"{r['arch']},{r['shape']},{r['mesh']},SKIP({r['skip'][:40]})")
            continue
        if "compute_s" not in r:
            continue
        print(f"{r['arch']},{r['shape']},{r['mesh']},"
              f"{r['compute_s']:.3e},{r['memory_s']:.3e},{r['collective_s']:.3e},"
              f"{r['dominant']},{r['flops_ratio']:.3f},{r['roofline_fraction']:.4f}")


# ------------------------------------------------------------ measured mode
# Sparse hot-path micro-benchmark geometry (smoke-scale but with a working
# set large enough that the pull/bag/push streams dominate the step).
MEASURE_GEOM = dict(rows=4096, dim=64, capacity=512, nnz=4096, bags=512)


def sparse_model_bytes(placement: str, fused: bool, *, capacity: int,
                       nnz: int, bags: int, dim: int, itemsize: int = 4,
                       accum_itemsize: int = 4) -> Dict[str, float]:
    """Analytic per-step HBM traffic of the sparse hot path (bytes).

    Counts the (rows x dim) streams each implementation moves through HBM —
    what the fusion actually changes — and ignores O(capacity)/O(nnz) index
    vectors.  Unfused materializes the gathered-embedding intermediate in
    the bag, the non-aliased updated-rows arrays in the push, and (cached)
    the slot-translated gather's extra pass; fused reads/writes each stream
    once, in place.  Backend-independent (unlike the measured cells).
    """
    row = dim * itemsize
    arow = dim * accum_itemsize
    # pull: table rows -> working set (read + write), once per step
    pull = capacity * row * 2
    if placement == "cached" and not fused:
        pull += capacity * row * 2       # slot-translate-then-gather pass
    # bag fwd: read the working-set stream, write the bags
    bag = nnz * row + bags * row
    if not fused:
        bag += nnz * row * 2             # gathered-embedding intermediate
    # push: delta/g2 streams + table/accum rows in, updated rows out.
    # Routed never fuses the push (the AdaGrad update runs shard-locally
    # inside the reverse route), so it keeps the unfused cost either way.
    push = capacity * (row + arow) * 2 + capacity * (row + arow)
    if not fused or placement == "routed":
        push += capacity * (row + arow)  # non-aliased updated-rows arrays
    return {"pull": float(pull), "bag": float(bag), "push": float(push),
            "total": float(pull + bag + push)}


def _hlo_op_count(compiled_text: str) -> int:
    """Instructions in the optimized HLO module (assignment lines)."""
    return len(re.findall(r"^\s+[%\w.\-]+ = ", compiled_text, re.M))


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }


def measure_cell(placement: str, fused: bool, steps: int = 30,
                 geom: Optional[Dict] = None) -> Dict:
    """One placement x fused cell: compile + time the sparse hot path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.embedding_backend import make_backend
    from repro.core.embedding_engine import EmbeddingEngine
    from repro.core.sparse_optim import SparseAdagrad
    from repro.kernels import ops

    g = dict(MEASURE_GEOM, **(geom or {}))
    rows, dim = g["rows"], g["dim"]
    capacity, nnz, bags = g["capacity"], g["nnz"], g["bags"]

    kwargs = {"cache_rows": capacity} if placement == "cached" else {}
    backend = make_backend(placement, fused=fused, **kwargs)
    opt = SparseAdagrad()

    rng = np.random.default_rng(0)
    # Zipf-skewed ids: the hot-head distribution the cache tier serves
    ids = jnp.asarray(
        np.minimum(rng.zipf(1.3, size=nnz) - 1, rows - 1), jnp.int32)
    seg = jnp.asarray(np.arange(nnz) % bags, jnp.int32)
    w = jnp.ones((nnz,), jnp.float32)
    table = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    accum = jnp.full((rows, dim), 0.1, jnp.float32)
    state = backend.init_state(table)

    def step(table, accum, state, ids):
        ws, table, accum, state = backend.pull(
            table, accum, state, ids, capacity)

        def loss(working):
            out = EmbeddingEngine.bag_from_working(
                working, ws.inverse, seg, bags, weights=w,
                combiner="sum", fused=fused)
            return jnp.sum(out * out)

        row_grads = jax.grad(loss)(ws.rows)
        table, accum, state = backend.push(
            table, accum, state, ws, row_grads, opt)
        return table, accum, state

    fn = jax.jit(step, donate_argnums=(0, 1, 2))
    compiled = fn.lower(table, accum, state, ids).compile()
    cell = {
        "placement": placement, "fused": fused,
        "kernel_mode": ops.kernel_mode() if fused else "xla",
        "hlo_ops": _hlo_op_count(compiled.as_text()),
        "model_bytes": sparse_model_bytes(
            placement, fused, capacity=capacity, nnz=nnz, bags=bags, dim=dim),
        **_cost_analysis(compiled),
    }
    # warm-up (also re-materializes donated buffers for the timed loop)
    table, accum, state = fn(table, accum, state, ids)
    jax.block_until_ready(table)
    t0 = time.perf_counter()
    for _ in range(steps):
        table, accum, state = fn(table, accum, state, ids)
    jax.block_until_ready(table)
    dt = time.perf_counter() - t0
    cell["steps_per_sec"] = steps / dt
    cell["us_per_step"] = dt / steps * 1e6
    return cell


def measure(steps: int = 30, geom: Optional[Dict] = None,
            placements=("gather", "routed", "cached")) -> Dict:
    """The full measured grid + analytic model, ready for BENCH_roofline.json."""
    import jax

    cells = [
        measure_cell(p, f, steps=steps, geom=geom)
        for p in placements for f in (False, True)
    ]
    return {
        "bench": "roofline_sparse_hot_path",
        "geom": dict(MEASURE_GEOM, **(geom or {})),
        "backend": jax.default_backend(),
        "steps_timed": steps,
        "cells": cells,
    }


def write_measure(out: str = "BENCH_roofline.json", steps: int = 30,
                  geom: Optional[Dict] = None) -> Dict:
    rec = measure(steps=steps, geom=geom)
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def measure_rows(quick: bool = False, out: str = "BENCH_roofline.json"):
    """benchmarks/run.py registry adapter: (name, us_per_call, derived) rows."""
    rec = write_measure(out, steps=10 if quick else 30)
    for c in rec["cells"]:
        name = f"roofline/{c['placement']}/{'fused' if c['fused'] else 'unfused'}"
        derived = (f"steps_s={c['steps_per_sec']:.2f} "
                   f"hlo_ops={c['hlo_ops']} "
                   f"model_MB={c['model_bytes']['total'] / 1e6:.3f} "
                   f"mode={c['kernel_mode']}")
        yield name, c["us_per_step"], derived


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("mesh", nargs="?", default="single",
                    help="dry-run mesh for the analytic table")
    ap.add_argument("--measure", action="store_true",
                    help="run the sparse hot-path micro-benchmark per "
                         "placement x {fused, unfused} and emit --out")
    ap.add_argument("--quick", action="store_true",
                    help="fewer timed steps (CI-speed)")
    ap.add_argument("--out", default="BENCH_roofline.json")
    args = ap.parse_args()
    if args.measure:
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
        print("name,us_per_step,derived")
        for name, us, derived in measure_rows(quick=args.quick, out=args.out):
            print(f"{name},{us:.1f},{derived}")
        print(f"# wrote {args.out}")
    else:
        print_table(mesh=args.mesh)
