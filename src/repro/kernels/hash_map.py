"""Device linear-probe hash map: the O(cache_rows) id→slot index.

``CachedBackend`` used to carry a dense ``(table_rows,)`` int32 id→slot
array on device — the last O(table_rows) device allocation in the cache
tier.  This module replaces it with an open-addressing linear-probe hash
map sized O(cache_rows), carried through the jitted pull/push as three
small arrays:

  - ``key_tab``  (H,) int32 — the id stored in each bucket (-1 = EMPTY),
  - ``slot_tab`` (H,) int32 — the cache slot that id mapped to,
  - ``n_occupied`` ()  int32 — occupied buckets, *including stale ones*.

Liveness is checked against ``slot_uid`` instead of deleting: an entry
``(k, s)`` is live iff ``slot_uid[s] == k``.  Eviction overwrites
``slot_uid[s]`` with the admitted id, which kills the evicted id's entry
for free — no tombstones, no unlink pass.  Buckets therefore only go
EMPTY → occupied; probe chains never shrink between rebuilds, which is
exactly what makes bounded probing *exact*:

  - **lookup** probes from ``h(k)`` until it sees ``k`` (at most one
    bucket per key can hold it) or an EMPTY bucket (the chain end);
  - **insert** of a key claims the first EMPTY bucket on its chain — or
    *reuses* the key's own stale bucket, which must appear before any
    EMPTY bucket on the chain (it was placed at a first-EMPTY position
    and nothing empties);
  - **rebuild** (when stale entries pile up past the occupancy bound)
    re-inserts only the live ``(slot_uid[s], s)`` pairs into fresh
    buckets, restoring load ≤ cache_rows / H.

``hash_table_size`` keeps H ≥ 4·cache_rows, and ``CachedBackend``
rebuilds before occupancy can cross 3H/4, so every chain ends in an
EMPTY bucket and both loops terminate.

The batch *lookup* is the hot-path kernel (``hash_lookup_pallas``, one
probe loop per working-set id, parity-locked to ``ref.hash_lookup_ref``
— dispatch via ``ops.hash_lookup`` per docs/kernels.md).  The map
*maintenance* (insert / rebuild) is trace-level jnp shared verbatim by
every dispatch mode: the map contents are bit-identical whether lookups
run through Pallas, the interpreter, or the jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_adagrad import LANES   # buckets per DMA'd row

EMPTY = -1  # bucket key for never-occupied buckets


def hash_table_size(cache_rows: int) -> int:
    """Bucket count H for a cache of ``cache_rows`` slots: the next power
    of two ≥ 4·cache_rows (load factor ≤ 0.25 after every rebuild), so
    probe chains stay short and an EMPTY chain-terminator always exists."""
    n = max(int(cache_rows), 8) * 4
    return 1 << (n - 1).bit_length()


def hash_bucket(keys: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Home bucket per key: 32-bit murmur3 finalizer, masked to H-1.

    The mix is a bijection on uint32 (distinct ids never alias before the
    mask), computed in wrapping uint32 so no x64 widening enters the jit.
    """
    assert n_buckets & (n_buckets - 1) == 0, "n_buckets must be a power of 2"
    x = keys.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x & jnp.uint32(n_buckets - 1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# maintenance (trace-level jnp — shared by every dispatch mode)
# ---------------------------------------------------------------------------

def hash_insert(key_tab, slot_tab, n_occupied, keys, slots, mask):
    """Batch-insert ``keys[i] -> slots[i]`` where ``mask[i]`` (keys under
    the mask are distinct and not live in the map).

    Round-based parallel probing: every pending key claims the first
    bucket on its chain that is EMPTY or already holds the key (a stale
    entry from a past residency — reused in place, so the map never holds
    two buckets for one key).  Conflicting claims on one EMPTY bucket are
    resolved by a deterministic scatter-max (highest key position wins);
    losers advance one probe and retry.  Terminates because every round
    either places a key or advances its probe toward an EMPTY bucket.
    """
    H = key_tab.shape[0]
    K = keys.shape[0]
    base = hash_bucket(keys, H)
    pos = jnp.arange(K, dtype=jnp.int32)

    def cond(carry):
        return jnp.any(carry[2])

    def body(carry):
        key_tab, slot_tab, pending, off, n_occ = carry
        b = (base + off) & (H - 1)
        kb = key_tab[b]
        reuse = pending & (kb == keys)           # own stale bucket: no conflict
        free = pending & (kb == EMPTY)
        winner = (
            jnp.full((H,), -1, jnp.int32)
            .at[jnp.where(free, b, H)]
            .max(pos, mode="drop")
        )
        won_free = free & (winner[b] == pos)
        won = reuse | won_free
        sink = jnp.where(won, b, H)
        key_tab = key_tab.at[sink].set(keys, mode="drop")
        slot_tab = slot_tab.at[sink].set(slots, mode="drop")
        n_occ = n_occ + jnp.sum(won_free.astype(jnp.int32))
        pending = pending & ~won
        off = jnp.where(pending, off + 1, off)
        return key_tab, slot_tab, pending, off, n_occ

    init = (key_tab, slot_tab, mask, jnp.zeros((K,), jnp.int32), n_occupied)
    key_tab, slot_tab, _, _, n_occupied = jax.lax.while_loop(cond, body, init)
    return key_tab, slot_tab, n_occupied


def hash_rebuild(slot_uid, n_buckets: int):
    """Fresh (key_tab, slot_tab, n_occupied) holding only the live
    ``(slot_uid[s], s)`` pairs — drops every stale entry in one shot."""
    C = slot_uid.shape[0]
    key_tab = jnp.full((n_buckets,), EMPTY, jnp.int32)
    slot_tab = jnp.zeros((n_buckets,), jnp.int32)
    return hash_insert(
        key_tab, slot_tab, jnp.zeros((), jnp.int32),
        slot_uid, jnp.arange(C, dtype=jnp.int32), slot_uid >= 0,
    )


# ---------------------------------------------------------------------------
# lookup kernel (the Pallas probe; jnp oracle lives in kernels/ref.py)
# ---------------------------------------------------------------------------

def _mix_scalar(u, hmask):
    x = u.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x & jnp.uint32(hmask)).astype(jnp.int32)


def _lookup_kernel(uids_ref, key_hbm, slot_hbm, suid_hbm, out_hbm,
                   kbuf, sbuf, xk, xs, cand, sem, *, hmask):
    """Probe ``tile`` working-set ids.  The map stays in HBM as rows of
    128 buckets; each id's home row is DMA'd into SMEM (all ids of the
    tile in flight at once), the chain is walked there (rows past the home
    row are fetched on demand), and a found slot resolves only if still
    live (``slot_uid[slot] == key``) — a stale hit is a miss and the probe
    stops (at most one bucket per key).  One tile is 128 ids, written
    back as one 128-lane output row."""
    tile = LANES
    base = pl.program_id(0) * tile

    def row_copy(src, row, dst):
        return pltpu.make_async_copy(src.at[pl.ds(row, 1)], dst, sem)

    def home(k):
        return _mix_scalar(uids_ref[base + k], hmask)

    def home_copies(k):
        row = home(k) // LANES
        return (row_copy(key_hbm, row, kbuf.at[pl.ds(k, 1)]),
                row_copy(slot_hbm, row, sbuf.at[pl.ds(k, 1)]))

    def for_tile(body):
        jax.lax.fori_loop(0, tile, lambda k, c: (body(k), c)[1], 0)

    for_tile(lambda k: [c.start() for c in home_copies(k)])
    for_tile(lambda k: [c.wait() for c in home_copies(k)])

    def walk(k):
        u = uids_ref[base + k]
        b0 = home(k)
        row0 = b0 // LANES

        def body(carry):
            off, xrow, _, _ = carry
            b = (b0 + off) & hmask
            row, lane = b // LANES, b % LANES
            in_home = row == row0

            @pl.when(jnp.logical_and(jnp.logical_not(in_home), row != xrow))
            def _fetch():
                cps = (row_copy(key_hbm, row, xk), row_copy(slot_hbm, row, xs))
                for c in cps:
                    c.start()
                for c in cps:
                    c.wait()

            kb = jnp.where(in_home, kbuf[k, lane], xk[0, lane])
            s = jnp.where(in_home, sbuf[k, lane], xs[0, lane])
            done = (kb == u) | (kb == EMPTY)
            return (off + 1, jnp.where(in_home, xrow, row),
                    done.astype(jnp.int32), jnp.where(kb == u, s, -1))

        neg = jnp.full((), -1, jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        _, _, _, s = jax.lax.while_loop(
            lambda c: c[2] == 0, body, (zero, neg, zero, neg))
        cand[k] = s

    for_tile(walk)

    # liveness: slot_uid[s] == key, one row DMA per candidate (into kbuf,
    # whose home rows are no longer needed)
    def live_copy(k):
        return row_copy(suid_hbm, cand[k] // LANES, kbuf.at[pl.ds(k, 1)])

    for_tile(lambda k: pl.when(cand[k] >= 0)(lambda: live_copy(k).start()))
    for_tile(lambda k: pl.when(cand[k] >= 0)(lambda: live_copy(k).wait()))

    def resolve(k):
        s = cand[k]
        live = (s >= 0) & (kbuf[k, s % LANES] == uids_ref[base + k])
        xk[0, k] = jnp.where(live, s, -1)

    for_tile(resolve)
    out = pltpu.make_async_copy(xk, out_hbm.at[pl.ds(pl.program_id(0), 1)],
                                sem)
    out.start()
    out.wait()


def _lane_rows(x: jnp.ndarray, fill: int) -> jnp.ndarray:
    """(N,) -> (ceil(N/128), 128): a free view when 128 divides N."""
    n = pl.cdiv(x.shape[0], LANES) * LANES
    if n != x.shape[0]:
        x = jnp.pad(x, (0, n - x.shape[0]), constant_values=fill)
    return x.reshape(n // LANES, LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_lookup_pallas(key_tab, slot_tab, slot_uid, uids, interpret=False):
    """slots[i] = live slot of uids[i], or -1 — the Pallas probe whose
    output feeds the fused cached gather/scatter index streams."""
    H = key_tab.shape[0]
    K = uids.shape[0]
    kp = pl.cdiv(K, LANES) * LANES
    if kp != K:
        uids = jnp.pad(uids, (0, kp - K))
    anyspec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_lookup_kernel, hmask=H - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(kp // LANES,),
            in_specs=[anyspec, anyspec, anyspec],
            out_specs=anyspec,
            scratch_shapes=[
                pltpu.SMEM((LANES, LANES), jnp.int32),  # home key rows
                pltpu.SMEM((LANES, LANES), jnp.int32),  # home slot rows
                pltpu.SMEM((1, LANES), jnp.int32),      # far key row / result
                pltpu.SMEM((1, LANES), jnp.int32),      # far slot row
                pltpu.SMEM((LANES,), jnp.int32),        # found slot per id
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((kp // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(uids, _lane_rows(key_tab, EMPTY), _lane_rows(slot_tab, 0),
      _lane_rows(slot_uid, -1))
    return out.reshape(-1)[:K]
