"""Operations and bytes that the work needs, from shapes.

The byte counts are the least any implementation must move: each distinct
input row read once, each output row written once, index vectors
included.  So no later kernel can read over 100% of its roofline, and the
counts do not go stale when an implementation changes.
"""

from __future__ import annotations

from typing import Tuple

F32 = 4


def bag(distinct_rows: int, nnz: int, bags: int, dim: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one weighted sum-bag call: ``nnz`` (id, bag,
    weight) entries over ``distinct_rows`` rows into ``bags`` outputs."""
    flops = 2.0 * nnz * dim
    moved = (distinct_rows * dim + 3 * nnz + bags * dim) * F32
    return flops, float(moved)


def push(distinct_rows: int, dim: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one AdaGrad push of ``distinct_rows`` rows: read
    the rows, their accumulator rows and their gradient (its square is
    computed, not read), write rows and accumulator rows back, read the
    row ids."""
    flops = 2.0 * distinct_rows * dim
    moved = (5 * distinct_rows * dim + distinct_rows) * F32
    return flops, float(moved)


def least_time(flops: float, moved: float, peak: dict) -> Tuple[float, str]:
    """(seconds, bound): the larger of FLOPs over peak FLOP/s and bytes
    over HBM bandwidth, and which of the two it is."""
    t_c = flops / peak["flops_bf16"]
    t_m = moved / peak["hbm_bytes_per_s"]
    return (t_m, "hbm") if t_m >= t_c else (t_c, "flops")


def step_flops_per_instance(model, cfg) -> float:
    """Model FLOPs of one instance per step: forward and backward of the
    trained batch (3x forward) plus the forward of the scored batch."""
    return 4.0 * model.forward_flops(cfg)
