"""Paper Fig. 10: communication ratio of k-step merging vs the baseline,
plus the sparse-placement wire accounting (routed vs GSPMD gather).

The paper measures model-transmission time ratio ~ 1/k (18.1%, 10.8%, 6.4%,
2.8%, 1.2% for k = 10..200).  We reproduce the byte accounting exactly: the
per-step cross-pod (DCN) bytes of the k-step scheme are the merge payload
amortized over k local steps, vs the every-step gradient sync of the
baseline (same payload every step).  Byte counts come from the compiled
multi-pod merge HLO (fig6 probe); the ratio is payload-independent.

The sparse rows quantify what ``--placement routed`` buys on the same
production mesh: one working-set pull+push compiled under GSPMD (row-
sharded table, value-blind masked-partials + all-reduce) vs the explicit
all_to_all request routing — per-device collective bytes and their ratio.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def probe(probe_args):
    """Run ``benchmarks._mesh_probe`` in a child pinned to the CPU (its 512
    virtual devices); a failed child raises, so the caller fails too."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks._mesh_probe"] + probe_args,
        capture_output=True, text=True, env=env, timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"_mesh_probe {' '.join(probe_args)} failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(payload_mb: float = 64.0):
    results = []
    rec = probe(["--probe", "merge", "--schedule", "two_phase",
                 "--payload-mb", str(payload_mb)])
    merge_dcn = rec["dcn_bytes_per_device"]
    # baseline: the same payload synchronizes cross-pod EVERY step
    for k in [10, 20, 50, 100, 200]:
        ratio = 1.0 / k
        results.append((
            f"fig10_k{k}", 0.0,
            f"per_step_dcn_MB={merge_dcn / k / 1e6:.4f},"
            f"ratio_vs_every_step={ratio:.4f},paper={_paper_ratio(k):.3f}",
        ))

    # --placement routed vs GSPMD gather: per-step sparse exchange bytes
    sparse = {
        p: probe(["--probe", "sparse", "--placement", p])
        for p in ("gather", "routed")
    }
    for p, rec in sparse.items():
        results.append((
            f"fig10_sparse_{p}", 0.0,
            f"total_MB_per_device={rec['total_bytes_per_device'] / 1e6:.4f},"
            f"dcn_MB={rec['dcn_bytes_per_device'] / 1e6:.4f},"
            f"ici_MB={rec['ici_bytes_per_device'] / 1e6:.4f}",
        ))
    g = sparse["gather"]["total_bytes_per_device"]
    r = sparse["routed"]["total_bytes_per_device"]
    results.append((
        "fig10_routed_vs_gspmd", 0.0,
        f"wire_ratio={r / max(g, 1):.4f},saving={1 - r / max(g, 1):.4f}",
    ))
    return results


def _paper_ratio(k: int) -> float:
    return {10: 0.181, 20: 0.108, 50: 0.064, 100: 0.028, 200: 0.012}[k]
