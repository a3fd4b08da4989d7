"""Fused embedding-bag kernel: out[b] = sum_{j: seg[j]==b} w[j] * working[inv[j]].

TPU adaptation of the FBGEMM-style table-batched embedding bag: the gather
runs over the *pulled working set*, fused with the segment reduction in one
kernel pass.  Two formulations share the wrapper:

- ``rows`` (real-TPU default): the nnz stream is sorted by bag outside the
  kernel, so each bag block reads one contiguous range of it.  The working
  set stays in HBM; each chunk of the range DMAs its rows into VMEM, and
  every weighted row is added into its bag in f32 on the VPU.  Numerically
  equivalent to, but not bit-identical with, the jnp segment-sum (XLA may
  order the adds differently).  A one-hot matmul on the MXU would do the
  same reduction in bf16 passes, which moved the loss measurably.
- ``exact`` (interpret default): in-kernel gather + drop-safe scatter-add
  into the bag block.  Adds values in exactly the order the XLA
  ``segment_sum`` oracle does, so it is bit-identical to the unfused bag —
  the formulation behind the fused-vs-unfused parity contract.  It holds
  the whole working set in one block, so it is for the interpreter only.

Block geometry is auto-selected and never constrained: the bag grid uses
``pl.cdiv`` (out-of-block segment ids are skipped in-kernel), and the nnz
stream is padded to the chunk size with weights=0 / seg=OOB, so arbitrary
batch/capacity geometries work instead of tripping shape asserts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_adagrad import lane_pad


def _bag_kernel_rows(bounds_ref, inv_hbm, seg_hbm, w_hbm, working_hbm,
                     out_ref, inv_s, seg_s, w_s, rows_v, sem, *,
                     bag_block: int, chunk: int):
    """One bag block: walk the chunks of the seg-sorted nnz stream that
    hold its bags, DMA each chunk's working rows from HBM, and add every
    weighted row into its bag in f32 on the VPU, in stream order."""
    i = pl.program_id(0)
    lo, hi = bounds_ref[i], bounds_ref[i + 1]
    out_ref[...] = jnp.zeros_like(out_ref)

    def row_copy(t):
        return pltpu.make_async_copy(
            working_hbm.at[pl.ds(inv_s[0, t], 1)], rows_v.at[pl.ds(t, 1)],
            sem)

    def each_row(body):
        jax.lax.fori_loop(0, chunk, lambda t, c: (body(t), c)[1], 0)

    def add_row(t):
        # entries of other bag blocks (the chunk's ends) are skipped
        b = seg_s[0, t] - i * bag_block

        @pl.when((b >= 0) & (b < bag_block))
        def _():
            out_ref[pl.ds(b, 1), :] += (
                rows_v[pl.ds(t, 1), :] * w_s[0, t].astype(rows_v.dtype))

    def body(c, carry):
        cps = [pltpu.make_async_copy(src.at[c], dst, sem)
               for src, dst in ((inv_hbm, inv_s), (seg_hbm, seg_s),
                                (w_hbm, w_s))]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()
        each_row(lambda t: row_copy(t).start())
        each_row(lambda t: row_copy(t).wait())
        each_row(add_row)
        return carry

    jax.lax.fori_loop(lo // chunk, pl.cdiv(hi, chunk), body, 0)


def _bag_rows(working, inv, seg, weights, *, num_bags, bag_block, chunk,
             interpret):
    D = working.shape[1]
    n_bag_blocks = pl.cdiv(num_bags, bag_block)
    nbp = n_bag_blocks * bag_block
    # sort the stream by bag so each bag block reads one contiguous range;
    # padded entries carry seg=nbp (after every block) and weight 0
    order = jnp.argsort(seg, stable=True)
    n = seg.shape[0]
    npad = pl.cdiv(n, chunk) * chunk - n
    seg_s = jnp.pad(seg[order], (0, npad), constant_values=nbp)
    inv_s = jnp.pad(inv[order], (0, npad))
    w_s = jnp.pad(weights[order], (0, npad))
    bounds = jnp.searchsorted(
        seg_s, jnp.arange(n_bag_blocks + 1, dtype=jnp.int32) * bag_block
    ).astype(jnp.int32)
    # (chunks, 1, chunk): a chunk is then a slice of the untiled dim
    rows = lambda x: x.reshape(-1, 1, chunk)
    wp = lane_pad(working)
    dp = wp.shape[1]
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    out = pl.pallas_call(
        functools.partial(_bag_kernel_rows, bag_block=bag_block, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_bag_blocks,),
            in_specs=[hbm] * 4,
            out_specs=pl.BlockSpec((bag_block, dp), lambda i, b: (i, 0)),
            scratch_shapes=[
                pltpu.SMEM((1, chunk), jnp.int32),     # row ids of a chunk
                pltpu.SMEM((1, chunk), jnp.int32),     # bag ids of a chunk
                pltpu.SMEM((1, chunk), jnp.float32),   # weights of a chunk
                pltpu.VMEM((chunk, dp), working.dtype),  # gathered rows
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nbp, dp), working.dtype),
        interpret=interpret,
    )(bounds, rows(inv_s), rows(seg_s), rows(w_s.astype(jnp.float32)), wp)
    return out[:num_bags, :D]


def _bag_rows_vmappable(num_bags, bag_block, chunk, interpret):
    """``_bag_rows`` with a batching rule that folds the vmapped axis into
    the stream: pod p's bags become bags ``p*num_bags + b`` of ONE call
    (and its rows ``p*C + r`` when the working set is batched too)."""
    kw = dict(bag_block=bag_block, chunk=chunk, interpret=interpret)

    @jax.custom_batching.custom_vmap
    def bag(working, inv, seg, weights):
        return _bag_rows(working, inv, seg, weights, num_bags=num_bags, **kw)

    @bag.def_vmap
    def _rule(axis_size, in_batched, working, inv, seg, weights):
        w_b, inv_b, seg_b, wt_b = in_batched
        full = lambda x, b: x if b else jnp.broadcast_to(
            x, (axis_size,) + x.shape)
        inv, seg, weights = full(inv, inv_b), full(seg, seg_b), full(
            weights, wt_b)
        p = jnp.arange(axis_size, dtype=jnp.int32)[:, None]
        if w_b:
            inv = inv + p * working.shape[1]
            working = working.reshape((-1,) + working.shape[2:])
        out = _bag_rows_vmappable(axis_size * num_bags, bag_block, chunk,
                                 interpret)(
            working, inv.reshape(-1), (seg + p * num_bags).reshape(-1),
            weights.reshape(-1))
        return out.reshape(axis_size, num_bags, -1), True

    return bag


def _bag_kernel_exact(inv_ref, seg_ref, w_ref, working_ref, out_ref, *,
                      bag_block: int, weighted: bool):
    i = pl.program_id(0)  # bag block; the whole nnz stream is one block
    emb = jnp.take(working_ref[...], inv_ref[...], axis=0)
    if weighted:
        emb = emb * w_ref[...][:, None].astype(emb.dtype)
    local = seg_ref[...] - i * bag_block
    # Out-of-block locals (either direction — negative indices would WRAP in
    # jnp scatter) route to the OOB index bag_block and are dropped.
    safe = jnp.where((local >= 0) & (local < bag_block), local, bag_block)
    out_ref[...] = jnp.zeros_like(out_ref).at[safe].add(emb, mode="drop")


def _auto_block(n: int, target: int) -> int:
    return max(1, min(target, n))


@functools.partial(
    jax.jit,
    static_argnames=("num_bags", "bag_block", "nnz_block", "interpret", "exact"),
)
def embedding_bag_pallas(
    working: jnp.ndarray,   # (C, D) pulled rows
    inv: jnp.ndarray,       # (nnz,) row index into working
    seg: jnp.ndarray,       # (nnz,) bag index (any order)
    weights: jnp.ndarray,   # (nnz,) or None
    num_bags: int,
    bag_block: int = 256,
    nnz_block: int = 512,
    interpret: bool = False,
    exact: bool | None = None,
) -> jnp.ndarray:
    C, D = working.shape
    nnz = inv.shape[0]
    if exact is None:
        exact = interpret  # bit-exact formulation wherever bits are checked
    bag_block = _auto_block(num_bags, bag_block)
    n_bag_blocks = pl.cdiv(num_bags, bag_block)
    nbp = n_bag_blocks * bag_block
    weighted = weights is not None
    if weights is None:
        weights = jnp.ones((nnz,), working.dtype)

    if exact:
        out = pl.pallas_call(
            functools.partial(
                _bag_kernel_exact, bag_block=bag_block, weighted=weighted
            ),
            grid=(n_bag_blocks,),
            in_specs=[
                pl.BlockSpec((nnz,), lambda i: (0,)),
                pl.BlockSpec((nnz,), lambda i: (0,)),
                pl.BlockSpec((nnz,), lambda i: (0,)),
                pl.BlockSpec((C, D), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((bag_block, D), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((nbp, D), working.dtype),
            interpret=interpret,
        )(inv, seg, weights, working)
        return out[:num_bags]

    # whole sublane tiles of bags, whole lane tiles of nnz
    bag_block = pl.cdiv(bag_block, 8) * 8
    chunk = pl.cdiv(nnz_block, 128) * 128
    return _bag_rows_vmappable(num_bags, bag_block, chunk, interpret)(
        working, inv, seg, weights)
