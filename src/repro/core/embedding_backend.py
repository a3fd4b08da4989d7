"""Pluggable sparse-parameter backends — one contract for the PS pull/push.

The paper's Algorithm 1 moves embedding rows, never tables: per batch the
trainer *pulls* the deduplicated working set, runs fwd/bwd against the
compact pulled rows, and *pushes* the row updates back.  How those rows
physically move is a placement decision, so it lives behind a protocol:

    state = backend.init_state(table)
    backend.pull(table, accum, state, flat_ids, capacity)
        -> (WorkingSet, table, accum, state)
    backend.lookup(table, accum, state, flat_ids, capacity)
        -> (WorkingSet, aux)              # read-only (serving/inference)
    backend.push(table, accum, state, working_set, row_grads, opt)
        -> (table, accum, state)
    backend.flush(table, accum, state) -> (table, accum, state)

The pull path is split into two explicit contracts.  ``pull`` is the
TRAINING pull: it may mutate backend state (LFU counters, cache
admissions/evictions, spill buffers) and thread updated tables back.
``lookup`` is the READ-ONLY serving lookup: it returns the same rows a
pull would serve but is side-effect-free on every input — no admissions,
no evictions, no counters, nothing donated — so a co-located inference
server can read the live training state between steps without perturbing
the training trajectory (ScaleFreeCTR's shared MixCache).  ``aux`` is a
small dict of f32 scalars metering the lookup itself (``serve_lookups``,
plus ``serve_misses`` for the cache tier) so serving traffic is counted
separately from training traffic.

Every backend owns an explicit per-table STATE pytree threaded through the
compiled train step (``EmbeddingEngine.pull/push`` -> ``HybridTrainer``).
Stateless placements carry an empty tuple; the cache tier carries its
id->slot map, frequency counters, and cached rows there.  ``pull`` may
write the table/accumulator (cache spills), ``flush`` forces any cached
dirty rows back (checkpoint/export consistency point), and ``prepare``/
``export`` convert between the logical row layout (row i == feature id i)
and whatever physical layout the backend shards by.  Three implementations:

``GatherBackend``
    The single-device / GSPMD path: ``jnp.unique`` dedup + one ``jnp.take``
    gather, push via ``SparseAdagrad.apply_rows``.  Logical layout; under
    GSPMD the gather lowers to masked partials + all-reduce (value-blind).
    With the Pallas push, a table narrower than 128 lanes is kept packed
    (``PackedRows``: several logical rows per 128-lane row).

``RoutedBackend``
    The paper's PS request routing on TPU: tables live hash-sharded
    (``slot_of`` spreads Zipf-hot heads uniformly), ids are bucketed by
    owning shard and exchanged with explicit ``all_to_all``s
    (``repro.core.routed_embedding``), so per-device wire is ~ rows moved
    once instead of ~2x the full working set.  Requests beyond the per-route
    bucket capacity are dropped-and-counted (``WorkingSet.n_dropped``) —
    the production overload signal; with ``cap_route`` at the worst case
    (the default) the exchange is lossless.

``CachedBackend`` (``repro.core.cache_tier``)
    The paper's §2.3 memory hierarchy: the full table + accumulator stay
    host-resident, a fixed-size device cache serves the Zipf-hot rows
    (LFU-with-decay admission/eviction, metered host<->device traffic).

All backends return identical results at lossless capacity (for the cache
tier: ``cache_rows >= table rows``) — asserted by
``tests/test_embedding_backend.py`` / ``tests/test_cache_tier.py`` — so
trainers switch placement with a config flag
(``TrainerConfig.placement`` / ``--placement``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import routed_embedding as routed
from repro.core.sparse_optim import SparseAdagrad
from repro.kernels import ops
from repro.kernels.sparse_adagrad import LANES, adagrad_row_updates
from repro.launch.mesh import _make_mesh


# --------------------------------------------------------------- working set
def pull_working_set(
    flat_ids: jnp.ndarray, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deduplicate the ids referenced by a batch (the PS "pull" manifest).

    Returns (unique_ids (capacity,), inverse (nnz,)) with static shapes:
    ``unique_ids`` is padded by repeating the smallest id (harmless for the
    scatter since padded slots receive zero gradient), ``inverse`` maps each
    original id slot to its row in the pulled working set.
    ``capacity`` must bound the number of distinct ids in a batch.
    """
    uids, inv = jnp.unique(
        flat_ids, size=capacity, fill_value=None, return_inverse=True
    )
    return uids.astype(jnp.int32), inv.astype(jnp.int32)


class WorkingSet(NamedTuple):
    """One table's pulled rows for one batch (Algorithm 1 line 3).

    ``rows`` carries one extra all-zero "drop" row at index ``capacity``:
    id slots that overflowed the dedup capacity have ``inverse ==
    capacity``, so their lookup reads zeros and the gradient landing on the
    drop row is discarded at push — training degrades gracefully (and
    countably) instead of NaN-poisoning on out-of-range gathers.
    """

    uids: jnp.ndarray       # (capacity,) int32 — deduplicated ids, padded
    inverse: jnp.ndarray    # (nnz,) int32 — original id slot -> working row
    rows: jnp.ndarray       # (capacity + 1, dim) — rows[i] = T[uids[i]];
                            # rows[capacity] == 0 (drop row)
    n_dropped: jnp.ndarray  # () int32 — ids not served (capacity overflow)


def _dedup(flat_ids: jnp.ndarray, capacity: int):
    """Dedup + overflow accounting shared by all backends.

    Returns (uids, inverse, n_dropped) where dropped slots (distinct ids
    beyond ``capacity`` — ``jnp.unique`` keeps the smallest) point at the
    zero drop row ``capacity`` instead of out of range.
    """
    uids, inv = pull_working_set(flat_ids, capacity)
    inv_c = jnp.clip(inv, 0, capacity - 1)
    served = jnp.take(uids, inv_c) == flat_ids
    inverse = jnp.where(served, inv_c, capacity)
    return uids, inverse, jnp.sum((~served).astype(jnp.int32))


def _with_drop_row(rows: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([rows, jnp.zeros((1, rows.shape[1]), rows.dtype)])


# ------------------------------------------------------------- packed rows
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["data"], meta_fields=["rows", "dim"])
@dataclasses.dataclass(frozen=True)
class PackedRows:
    """A (rows, dim) table stored ``f = 128 // dim`` logical rows to each
    128-lane physical row: logical row r is lanes ``(r % f) * dim`` to
    ``(r % f + 1) * dim`` of ``data[r // f]``.

    A table narrower than a lane tile has no compact row layout on a TPU:
    XLA keeps (R, 64) transposed, and every row gather, and the Pallas push
    (which DMAs whole lane tiles), first copies or pads the whole table to
    128 lanes.  Packed, the table and its accumulator are lane-dense at
    their logical size, so no step relays them out.  A pytree whose only
    leaf is ``data``; the logical shape rides in the treedef."""

    data: jnp.ndarray    # (ceil(rows / f), 128)
    rows: int
    dim: int

    @property
    def f(self) -> int:
        return LANES // self.dim


def row_packing(table) -> int:
    """Logical rows per physical row of a table (1: row layout)."""
    return table.f if isinstance(table, PackedRows) else 1


def _lane_group(wide: jnp.ndarray, lane: jnp.ndarray, f: int, dim: int):
    """(n, 128) physical rows -> (n, dim): lane group ``lane[i]`` of row i."""
    out = wide[:, :dim]
    for j in range(1, f):
        out = jnp.where((lane == j)[:, None], wide[:, j * dim:(j + 1) * dim],
                        out)
    return out


def _in_lane_group(x: jnp.ndarray, lane: jnp.ndarray, f: int):
    """(n, dim) rows -> (n, 128): row i in lane group ``lane[i]``, -0.0 in
    the others.  -0.0 is the additive identity of IEEE floats (x + -0.0 is
    x for every x, +0.0 included), so adding such a row changes only the
    lanes it owns."""
    return jnp.concatenate(
        [jnp.where((lane == j)[:, None], x, -0.0) for j in range(f)], axis=1)


class PackCounters(NamedTuple):
    """Cumulative push counters of one packed table (f32, on the device)."""

    merged: jnp.ndarray   # distinct rows folded into an earlier row's write
    pushed: jnp.ndarray   # distinct rows pushed


def _push_packed(table: PackedRows, accum: PackedRows, counters, uids,
                 grads, opt: SparseAdagrad):
    """The fused push on packed rows: the unfused scatter's bits, one
    lane-dense Pallas pass.

    ``adagrad_row_updates`` runs on the logical (cap, dim) rows as on any
    table.  Each (delta, g2) row goes into its lane group of a 128-lane row,
    and the rows of one physical row are summed into the first of them.
    ``uids`` are sorted ascending (pads, which repeat ``uids[0]``, at the
    end), so those rows are adjacent, at most ``f`` of them.  The others
    then point at ``uids[0]``'s physical row with a zero update: the only
    repeats the kernel sees are pads of entry 0, its index contract
    (``sparse_adagrad_apply_pallas``), so no two of its DMAs race on a row.
    """
    f, dim = table.f, table.dim
    n = uids.shape[0]
    phys, lane = uids // f, uids % f
    delta, g2 = adagrad_row_updates(
        _lane_group(jnp.take(accum.data, phys, axis=0), lane, f, dim),
        grads, table.data.dtype, lr=opt.cfg.lr, eps=opt.cfg.eps)
    live = (jnp.arange(n) == 0) | (uids != uids[0])
    first = live & jnp.concatenate(
        [jnp.ones((1,), bool), phys[1:] != phys[:-1]])
    wd, wg = _in_lane_group(delta, lane, f), _in_lane_group(g2, lane, f)
    fd, fg = wd, wg
    for j in range(1, f):           # fold entry i + j into entry i
        same = jnp.concatenate([(phys[j:] == phys[:-j]) & live[j:],
                                jnp.zeros((j,), bool)])[:, None]
        tail = jnp.full((j, LANES), -0.0, wd.dtype)
        fd = fd + jnp.where(same, jnp.concatenate([wd[j:], tail]), -0.0)
        fg = fg + jnp.where(same, jnp.concatenate([wg[j:], tail]), -0.0)
    idx = jnp.where(first, phys, phys[0])
    new_t, new_a = ops.scatter_row_updates(
        table.data, accum.data, idx,
        jnp.where(first[:, None], fd, 0.0), jnp.where(first[:, None], fg, 0.0))
    counters = PackCounters(
        counters.merged + jnp.sum(live & ~first).astype(jnp.float32),
        counters.pushed + jnp.sum(live).astype(jnp.float32))
    return (dataclasses.replace(table, data=new_t),
            dataclasses.replace(accum, data=new_a), counters)


@runtime_checkable
class EmbeddingBackend(Protocol):
    """Placement strategy for one embedding table.

    All pull/push/flush methods must be jit-traceable (they run inside the
    compiled train step), take and return the per-table backend state
    pytree from ``init_state`` (empty tuple for stateless placements), and
    thread the table + AdaGrad accumulator through so a backend may write
    them (cache spills/flushes).  ``push`` applies the sparse optimizer
    update itself so a backend can fuse it with the reverse route
    (RoutedBackend updates rows shard-locally, exactly where they live) or
    with its cache (CachedBackend writes through to hot rows only).
    """

    def init_state(self, table: jnp.ndarray):
        """Per-table backend state pytree (empty tuple if stateless)."""
        ...

    def prepare(self, table: jnp.ndarray) -> jnp.ndarray:
        """Logical row layout -> this backend's physical layout."""
        ...

    def export(self, table: jnp.ndarray) -> jnp.ndarray:
        """Physical layout -> logical rows (checkpoint export / parity)."""
        ...

    def flush(self, table, accum, state):
        """Force deferred writes (cached dirty rows) back into table/accum."""
        ...

    def pull(self, table, accum, state, flat_ids, capacity: int):
        ...

    def lookup(self, table, accum, state, flat_ids, capacity: int):
        """Read-only serving lookup: ``(WorkingSet, aux)``.

        Must serve the same row values a ``pull`` would (cache-fresh rows
        included) while mutating NOTHING — no admission, no eviction, no
        counter writes; every input pytree is returned untouched by simply
        not being returned at all."""
        ...

    def push(self, table, accum, state, ws: WorkingSet, row_grads,
             opt: SparseAdagrad):
        ...


# ------------------------------------------------------------------- gather
class GatherBackend:
    """Dedup + ``jnp.take`` pull, scatter-AdaGrad push (logical layout).

    The right choice on one device and the baseline under GSPMD: the
    compiler partitions the gather/scatter over a row-sharded table, at the
    cost of value-blind all-reduce traffic (see RoutedBackend).  Stateless:
    the backend-state pytree is an empty tuple.

    ``fused=True`` routes the push through the fused Pallas scatter+AdaGrad
    kernel (``kernels.ops.sparse_adagrad_apply``): the row update is applied
    straight into the aliased table/accumulator buffers instead of
    materializing the intermediate updated-rows arrays — bit-identical to
    the unfused scatter (same pinned row math feeds both).  Where that
    kernel runs (not ``kernel_mode() == "ref"``) and the width divides 128
    lanes, ``prepare`` packs the table (``PackedRows``; the accumulator
    follows its shape), pull and lookup select each row's lane group, and
    the push folds the rows that share a physical row into one write; the
    state then counts those folds (``stats``: ``packed_merged`` of
    ``packed_pushed`` distinct rows).

    ``staged=True`` is the DiskStore dataflow (``--store disk``): the
    ``table``/``accum`` the jitted pull/push see are NOT the full table but
    the batch's (capacity, dim) working-set rows, staged by the
    ``RowStore`` in dedup'd-uid order.  Pull just appends the drop row;
    push applies the same AdaGrad row math elementwise
    (``SparseAdagrad.apply_staged``) and returns the updated rows through
    the table/accum outputs for the host to commit.  Bit-identical to the
    resident path at every valid (first-occurrence) position.
    """

    def __init__(self, fused: bool = False, staged: bool = False):
        self.fused = fused
        self.staged = staged

    def _packing(self, dim: int) -> int:
        """Rows per 128-lane row for a table ``dim`` wide: packed where the
        push runs through the Pallas kernel and ``dim`` divides 128."""
        if (self.fused and not self.staged and ops.kernel_mode() != "ref"
                and dim < LANES and LANES % dim == 0):
            return LANES // dim
        return 1

    def init_state(self, table):
        if isinstance(table, PackedRows):
            return PackCounters(jnp.zeros((), jnp.float32),
                                jnp.zeros((), jnp.float32))
        return ()

    def stats(self, state) -> dict:
        """A packed table's push counters as python floats (outside jit)."""
        if not isinstance(state, PackCounters):
            return {}
        got = jax.device_get(state)
        return {"packed_merged": float(got.merged),
                "packed_pushed": float(got.pushed)}

    def prepare(self, table: jnp.ndarray):
        rows, dim = table.shape
        f = self._packing(dim)
        if f == 1:
            return table
        # packed in numpy: on a TPU the (rows, dim) table is laid out
        # transposed, and reshaping it to 128-lane rows there takes a
        # temporary twice the table's size; on the host the reshape is free
        host = np.asarray(jax.device_get(table))
        data = np.pad(host, ((0, -rows % f), (0, 0))).reshape(-1, LANES)
        return PackedRows(jax.device_put(data), rows, dim)

    def export(self, table) -> jnp.ndarray:
        if isinstance(table, PackedRows):
            return table.data.reshape(-1, table.dim)[: table.rows]
        return table

    def flush(self, table, accum, state):
        return table, accum, state

    def _served_rows(self, table, uids, capacity: int) -> jnp.ndarray:
        """(capacity + 1, dim) rows for ``uids`` — shared by pull/lookup."""
        if isinstance(table, PackedRows):
            f = table.f
            wide = jnp.take(table.data, uids // f, axis=0)
            return _with_drop_row(_lane_group(wide, uids % f, f, table.dim))
        if self.staged:
            if table.shape[0] != capacity:
                raise ValueError(
                    f"staged pull expects ({capacity}, dim) working-set rows "
                    f"from the RowStore, got {table.shape}"
                )
            # the store already gathered rows in dedup'd-uid order — the
            # host mirrors _dedup exactly (np.unique, truncate-keep-smallest,
            # pad with the minimum), so rows[i] IS T[uids[i]]
            return _with_drop_row(table)
        return _with_drop_row(jnp.take(table, uids, axis=0))

    def pull(self, table, accum, state, flat_ids, capacity: int):
        uids, inv, n_dropped = _dedup(flat_ids, capacity)
        rows = self._served_rows(table, uids, capacity)
        return WorkingSet(uids, inv, rows, n_dropped), table, accum, state

    def lookup(self, table, accum, state, flat_ids, capacity: int):
        """Read-only lookup: identical row service to ``pull`` (the gather
        path is stateless, so the only difference is the contract — nothing
        is threaded back, nothing may be donated into it)."""
        uids, inv, n_dropped = _dedup(flat_ids, capacity)
        rows = self._served_rows(table, uids, capacity)
        aux = {"serve_lookups":
               jnp.float32(flat_ids.size) - n_dropped.astype(jnp.float32)}
        return WorkingSet(uids, inv, rows, n_dropped), aux

    def push(self, table, accum, state, ws: WorkingSet, row_grads,
             opt: SparseAdagrad):
        # row_grads[capacity] belongs to the drop row — discard it.
        if isinstance(table, PackedRows):
            return _push_packed(table, accum, state, ws.uids,
                                row_grads[: ws.uids.shape[0]], opt)
        if self.staged:
            # elementwise AdaGrad on the staged rows; the updated buffers
            # ride out through the table/accum outputs and the host commits
            # the valid positions into the DiskStore at the next boundary
            new_table, new_accum = opt.apply_staged(
                table, accum, row_grads[: ws.uids.shape[0]]
            )
        else:
            new_table, new_accum = opt.apply_rows(
                table, accum, ws.uids, row_grads[: ws.uids.shape[0]],
                fused=self.fused,
            )
        return new_table, new_accum, state


# ------------------------------------------------------------------- routed
class RoutedBackend:
    """Topology-routed all-to-all pull/push over a hash-sharded table.

    Parameters
    ----------
    mesh: the device mesh the table is row-sharded over.
    shard_axes: mesh axes forming the shard dimension (axes absent from the
        mesh are ignored, so one spec works for single- and multi-pod runs).
    cap_route: per-(requester, owner) bucket capacity.  ``None`` (default)
        uses the worst case — every local id addressing one shard — which
        makes the exchange lossless; smaller values bound the exchange
        buffers and drop-and-count overflow like an overloaded PS shard.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        shard_axes: Tuple[str, ...] = ("data", "model"),
        cap_route: Optional[int] = None,
    ):
        self.mesh = mesh
        self.shard_axes = tuple(a for a in shard_axes if a in mesh.axis_names)
        n = 1
        for a in self.shard_axes:
            n *= mesh.shape[a]
        self.n_shards = n
        self.cap_route = cap_route
        self._fns = {}

    def _check_divisible(self, what: str, value: int):
        if value % self.n_shards:
            raise ValueError(
                f"RoutedBackend: {what} ({value}) must be divisible by "
                f"n_shards ({self.n_shards})"
            )

    def _pull_push(self, rows: int, dim: int, capacity: int):
        key = (rows, dim, capacity)
        if key not in self._fns:
            self._check_divisible("table rows", rows)
            self._check_divisible("capacity", capacity)
            cap_local = capacity // self.n_shards
            cap_route = self.cap_route if self.cap_route is not None else cap_local
            self._fns[key] = routed.make_routed_pull_push(
                self.mesh, rows // self.n_shards, dim, cap_local, cap_route,
                shard_axes=self.shard_axes,
            )
        return self._fns[key]

    def _perm(self, rows: int) -> jnp.ndarray:
        """logical id -> physical slot (hash-sharding bijection)."""
        self._check_divisible("table rows", rows)
        return routed.slot_of(
            jnp.arange(rows, dtype=jnp.int32), rows // self.n_shards, self.n_shards
        )

    def init_state(self, table: jnp.ndarray):
        return ()

    def prepare(self, table: jnp.ndarray) -> jnp.ndarray:
        perm = self._perm(table.shape[0])
        return jnp.zeros_like(table).at[perm].set(table)

    def export(self, table: jnp.ndarray) -> jnp.ndarray:
        return jnp.take(table, self._perm(table.shape[0]), axis=0)

    def flush(self, table, accum, state):
        return table, accum, state

    def pull(self, table, accum, state, flat_ids, capacity: int):
        uids, inv, n_dedup_dropped = _dedup(flat_ids, capacity)
        pull_fn, _ = self._pull_push(table.shape[0], table.shape[1], capacity)
        rows, _, dropped = pull_fn(table, uids)
        ws = WorkingSet(
            uids, inv, _with_drop_row(rows), n_dedup_dropped + jnp.sum(dropped)
        )
        return ws, table, accum, state

    def lookup(self, table, accum, state, flat_ids, capacity: int):
        """Read-only lookup: the same all-to-all exchange as ``pull`` (the
        route reads shard-resident rows and mutates nothing), returned
        without the state threading so nothing can be donated into it."""
        uids, inv, n_dedup_dropped = _dedup(flat_ids, capacity)
        pull_fn, _ = self._pull_push(table.shape[0], table.shape[1], capacity)
        rows, _, dropped = pull_fn(table, uids)
        n_dropped = n_dedup_dropped + jnp.sum(dropped)
        ws = WorkingSet(uids, inv, _with_drop_row(rows), n_dropped)
        aux = {"serve_lookups":
               jnp.float32(flat_ids.size) - n_dropped.astype(jnp.float32)}
        return ws, aux

    def push(self, table, accum, state, ws: WorkingSet, row_grads,
             opt: SparseAdagrad):
        _, push_fn = self._pull_push(
            table.shape[0], table.shape[1], ws.uids.shape[0]
        )
        new_table, new_accum, _ = push_fn(
            table, accum, ws.uids, row_grads[: ws.uids.shape[0]],
            opt.cfg.lr, opt.cfg.eps,
        )
        return new_table, new_accum, state


# ------------------------------------------------------------------ factory
def make_backend(
    placement: str,
    mesh: Optional[jax.sharding.Mesh] = None,
    fused: bool = False,
    **kwargs,
) -> EmbeddingBackend:
    """``placement`` in {"gather", "routed", "cached"} -> a backend instance.

    ``routed`` without an explicit mesh builds a 1-D mesh over all local
    devices (on one CPU device that degenerates to n_shards=1, where the
    routed exchange is bit-identical to the gather path — the parity the
    tests and the ``--placement`` acceptance check rely on).  ``cached``
    takes ``cache_rows`` (device cache size, required) and ``decay``
    (LFU decay, optional) — see ``repro.core.cache_tier.CachedBackend``.
    ``staged=True`` (gather/cached; plus ``capacity`` for cached) selects
    the DiskStore dataflow where pull/push see staged working-set rows
    instead of the resident table — wired by ``runtime.factory`` when
    ``store="disk"``.

    ``fused`` selects the fused Pallas pull/push kernels where a placement
    has them (gather: fused push; cached: fused pull + push with the
    id→slot indirection folded in).  The routed push computes AdaGrad
    shard-locally inside its reverse all_to_all route (a different fusion
    boundary already), so ``fused`` is accepted but a no-op there — routed
    training still gets the fused embedding *bag* at the engine layer.
    """
    if placement == "gather":
        # mesh is legitimate shared context (GSPMD shards the gather);
        # placement-specific knobs are not — dropping them silently would
        # make a capacity-bounded experiment run unbounded.
        staged = kwargs.pop("staged", False)
        if kwargs:
            raise TypeError(
                f"placement 'gather' does not accept {sorted(kwargs)} "
                f"(routed/cached-only options)"
            )
        return GatherBackend(fused=fused, staged=staged)
    if placement == "routed":
        if mesh is None:
            mesh = _make_mesh((jax.device_count(),), ("data",))
        return RoutedBackend(mesh, **kwargs)
    if placement == "cached":
        from repro.core.cache_tier import CachedBackend

        if "cache_rows" not in kwargs:
            raise TypeError("placement 'cached' requires cache_rows")
        return CachedBackend(fused=fused, **kwargs)
    raise ValueError(
        f"unknown placement {placement!r}; use 'gather', 'routed', or 'cached'"
    )
