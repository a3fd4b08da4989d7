"""Fused working-set sparse-AdaGrad kernels (the PS "push" math, paper §5).

Two layers:

``sparse_adagrad_pallas`` operates on a dense pulled row block: given
(rows, accum, grads) of the working set it produces updated rows and
accumulators in one fused element-wise pass —
``a' = a + g^2;  w' = w - lr * g / (sqrt(a') + eps)``.  Grid over row
blocks (uneven trailing blocks are masked by Pallas, so any (C, D)
geometry works).

``sparse_adagrad_apply_pallas`` is the *scatter* push used by the real
hot path: it applies per-row (delta, g2) updates directly into the full
(rows, dim) table/accumulator, which stay in HBM and are aliased to the
outputs, so no intermediate updated-rows array is materialized.  Each grid
step DMAs one tile of indexed rows into VMEM, adds, and DMAs them back.
The AdaGrad arithmetic itself is computed ONCE outside the kernel by
:func:`adagrad_row_updates` (shared with the unfused
``SparseAdagrad.apply_rows``) and the kernel body is pure data movement
(``add`` of two loads) — that is what makes the fused push bit-identical
to the unfused scatter on every backend: LLVM/XLA cannot re-contract a
mul+add into an FMA when the kernel never sees the mul.

``pull_working_set`` pads ``uids`` with copies of the first (minimum) id,
and pads carry zero gradient, so the kernel does not write a row that
repeats entry 0: the rows it writes are distinct, and no two of its DMAs
race on one row.

``sparse_adagrad_cached_apply_pallas`` / ``gather_rows_cached_pallas``
are the cache-tier uses of the same kernels: the id→slot hash-probe output
(``kernels.hash_map.hash_lookup_pallas``) is the index stream, so the
cached pull/push do one indexed pass over the (slots, dim) cache instead of
materializing slot-translated row gathers around the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def adagrad_row_updates(accum_rows, grads, table_dtype, *, lr, eps):
    """The AdaGrad row math, pinned against FMA re-association.

    Returns ``(delta, g2)`` with ``delta = -lr * g / (sqrt(a + g^2) + eps)``
    cast to the table dtype.  The two ``optimization_barrier``s force g2 and
    delta to materialize exactly once, so the *same* bits feed both the
    unfused ``.at[].add`` scatter and the fused Pallas apply — without them
    XLA fuses the delta computation into the scatter and single-rounds it
    (recip+FMA), breaking fused-vs-unfused bit identity.
    """
    g = grads.astype(jnp.float32)
    g2 = jax.lax.optimization_barrier(jnp.square(g))
    a_new = accum_rows + g2
    delta = -lr * g / (jnp.sqrt(a_new) + eps)
    delta = jax.lax.optimization_barrier(delta.astype(table_dtype))
    return delta, g2


def _adagrad_kernel(w_ref, a_ref, g_ref, nw_ref, na_ref, *, lr, eps):
    g = g_ref[...].astype(jnp.float32)
    a = a_ref[...] + g * g
    w = w_ref[...].astype(jnp.float32) - lr * g / (jnp.sqrt(a) + eps)
    nw_ref[...] = w.astype(nw_ref.dtype)
    na_ref[...] = a


@functools.partial(
    jax.jit, static_argnames=("lr", "eps", "row_block", "interpret")
)
def sparse_adagrad_pallas(
    rows: jnp.ndarray,    # (C, D) pulled table rows
    accum: jnp.ndarray,   # (C, D) f32
    grads: jnp.ndarray,   # (C, D)
    lr: float = 0.05, eps: float = 1e-10,
    row_block: int = 512, interpret: bool = False,
):
    C, D = rows.shape
    # Any geometry: cdiv grid, Pallas masks the uneven trailing block.
    row_block = max(1, min(row_block, C))
    spec = pl.BlockSpec((row_block, D), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_adagrad_kernel, lr=lr, eps=eps),
        grid=(pl.cdiv(C, row_block),),
        in_specs=[spec] * 3,
        out_specs=[spec] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((C, D), rows.dtype),
            jax.ShapeDtypeStruct((C, D), jnp.float32),
        ],
        interpret=interpret,
    )(rows, accum, grads)


# ---------------------------------------------------------------------------
# index-stream row kernels: the table stays in HBM, rows move by DMA
# ---------------------------------------------------------------------------

LANES = 128


def lane_pad(x: jnp.ndarray) -> jnp.ndarray:
    """(N, D) -> (N, Dp) with Dp the next multiple of 128 lanes.

    Mosaic slices an HBM array only in whole lane tiles, so a row narrower
    than 128 lanes (baidu-ctr's D=64) is DMA'd from a lane-padded copy.
    The padded lanes are sliced off again by the callers."""
    d = x.shape[1]
    dp = pl.cdiv(d, LANES) * LANES
    return x if dp == d else jnp.pad(x, ((0, 0), (0, dp - d)))


def row_tile(n: int, target: int = 128) -> int:
    """Rows per grid step: whole 8-row sublane tiles, at most ``target``."""
    return min(target, pl.cdiv(n, 8) * 8)


def _pad_to(x: jnp.ndarray, n: int, value=0) -> jnp.ndarray:
    if x.shape[0] == n:
        return x
    widths = ((0, n - x.shape[0]),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


def _fori(n: int, body):
    jax.lax.fori_loop(0, n, lambda k, c: (body(k), c)[1], 0)


def _gather_kernel(idx_ref, src_hbm, out_ref, sem, *, tile):
    base = pl.program_id(0) * tile

    def copy(k):
        return pltpu.make_async_copy(
            src_hbm.at[pl.ds(idx_ref[base + k], 1)],
            out_ref.at[pl.ds(k, 1)], sem)

    _fori(tile, lambda k: copy(k).start())   # every row of the tile in flight
    _fori(tile, lambda k: copy(k).wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_pallas(src: jnp.ndarray, idx: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """out[i] = src[idx[i]]: (src rows, D) in HBM, one row DMA per index
    into a (tile, Dp) VMEM output block."""
    K, D = idx.shape[0], src.shape[1]
    tile = row_tile(K)
    kp = pl.cdiv(K, tile) * tile
    srcp = lane_pad(src)
    dp = srcp.shape[1]
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(kp // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, dp), lambda i, idx: (i, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((kp, dp), src.dtype),
        interpret=interpret,
    )(_pad_to(idx, kp), srcp)
    return out[:K, :D]


def _apply_kernel(idx_ref, d_ref, g2_ref, t_in, a_in, t_ref, a_ref,
                  tbuf, abuf, sem, *, tile):
    """Read the tile's rows, add (delta, g2), write them back.

    Pure data movement plus two adds of LOADS (delta/g2 precomputed
    outside), so the result is bit-identical to the jnp scatter-add.  A
    row that repeats entry 0's row is a capacity pad (zero update, see
    ``sparse_adagrad_apply_pallas``) and is not written: every written row
    is then distinct, so no two DMAs of one call ever race on a row."""
    del t_in, a_in           # aliased with t_ref / a_ref
    base = pl.program_id(0) * tile
    first = idx_ref[0]

    def pairs(k):            # (HBM row, VMEM row) of table and accumulator
        r = idx_ref[base + k]
        return ((t_ref.at[pl.ds(r, 1)], tbuf.at[pl.ds(k, 1)]),
                (a_ref.at[pl.ds(r, 1)], abuf.at[pl.ds(k, 1)]))

    def read(k):
        return [pltpu.make_async_copy(h, v, sem) for h, v in pairs(k)]

    def write(k):
        return [pltpu.make_async_copy(v, h, sem) for h, v in pairs(k)]

    def start(copies):
        for c in copies:
            c.start()

    def wait(copies):
        for c in copies:
            c.wait()

    def unless_pad(k, fn):
        pl.when((base + k == 0) | (idx_ref[base + k] != first))(fn)

    _fori(tile, lambda k: start(read(k)))
    _fori(tile, lambda k: wait(read(k)))
    tbuf[...] = tbuf[...] + d_ref[...]
    abuf[...] = abuf[...] + g2_ref[...]
    _fori(tile, lambda k: unless_pad(k, lambda: start(write(k))))
    _fori(tile, lambda k: unless_pad(k, lambda: wait(write(k))))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_adagrad_apply_pallas(
    table: jnp.ndarray,   # (R, D) full table
    accum: jnp.ndarray,   # (R, D) f32 accumulator
    uids: jnp.ndarray,    # (cap,) row ids; repeats of uids[0] are pads
    delta: jnp.ndarray,   # (cap, D) table-dtype update, from adagrad_row_updates
    g2: jnp.ndarray,      # (cap, D) f32 squared grads
    interpret: bool = False,
):
    """table[uids] += delta, accum[uids] += g2, in place in HBM.

    Index contract (``pull_working_set``'s and the cache tier's): the only
    repeated rows are pads that repeat ``uids[0]`` and carry a zero
    update, so skipping their writes equals the jnp scatter-add."""
    cap, D = uids.shape[0], table.shape[1]
    tile = row_tile(cap)
    kp = pl.cdiv(cap, tile) * tile
    tp, ap = lane_pad(table), lane_pad(accum)
    dp = tp.shape[1]
    uids = _pad_to(uids, kp, value=uids[0])
    delta = _pad_to(lane_pad(delta), kp)
    g2 = _pad_to(lane_pad(g2), kp)
    blk = pl.BlockSpec((tile, dp), lambda i, idx: (i, 0))
    anyspec = pl.BlockSpec(memory_space=pl.ANY)
    new_t, new_a = pl.pallas_call(
        functools.partial(_apply_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(kp // tile,),
            in_specs=[blk, blk, anyspec, anyspec],
            out_specs=[anyspec, anyspec],
            scratch_shapes=[pltpu.VMEM((tile, dp), table.dtype),
                            pltpu.VMEM((tile, dp), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct(tp.shape, table.dtype),
                   jax.ShapeDtypeStruct(ap.shape, jnp.float32)],
        # alias indices count the scalar-prefetch arg: uids=0, table=3, accum=4
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(uids, delta, g2, tp, ap)
    return new_t[:, :D], new_a[:, :D]


# The cache tier's push is the same kernel over (cache_rows, cache_accum),
# driven by the hash probe's slot stream: pads share the first id's slot.
sparse_adagrad_cached_apply_pallas = sparse_adagrad_apply_pallas


def gather_rows_cached_pallas(cache_rows, slots, interpret: bool = False):
    """out[i] = cache_rows[slots[i]] — the fused cached pull, indexed by
    the hash-probe output stream."""
    return gather_rows_pallas(cache_rows, slots, interpret=interpret)
