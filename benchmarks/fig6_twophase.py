"""Paper Fig. 6 (+7): two-phase communication vs the naive route.

The paper's two-phase GPU communication keeps bulk traffic on NVLink; our
TPU adaptation keeps it on in-pod ICI.  This benchmark compiles one k-step
merge of a 64 MB dense tower on the 512-chip multi-pod mesh under each
schedule and reports the slow-fabric (DCN) bytes per device — the quantity
the paper's Fig. 6/7 measure in time.  Runs in a subprocess (512 fake
devices).
"""

from __future__ import annotations

import time

from benchmarks.fig10_comm_ratio import probe


def run(payload_mb: float = 64.0):
    results = []
    base = None
    for schedule in ["flat", "two_phase", "bf16", "int8_ef"]:
        t0 = time.perf_counter()
        rec = probe(["--probe", "merge", "--schedule", schedule,
                     "--payload-mb", str(payload_mb)])
        us = (time.perf_counter() - t0) * 1e6
        dcn = rec["dcn_bytes_per_device"]
        if schedule == "flat":
            base = dcn
        ratio = f",dcn_vs_flat={dcn / base:.4f}" if base else ""
        results.append((
            f"fig6_merge_{schedule}", us,
            f"dcn_MB_per_dev={dcn / 1e6:.3f},ici_MB_per_dev="
            f"{rec['ici_bytes_per_device'] / 1e6:.3f}{ratio}",
        ))
    return results
