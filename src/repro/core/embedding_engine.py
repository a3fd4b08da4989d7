"""Embedding engine — the single facade over the sparse-parameter path.

The TPU rendering of the paper's hierarchical parameter server (§2.3):
terabyte-class tables row-sharded across the mesh, trained through per-batch
*working-set pulls* (each instance references ~100 of 1e11 features, so
compute and communication scale with the deduplicated working set, never
with the table).  The engine owns everything sparse:

  - the ``TableSpec``s (shape, combiner, which batch field feeds each table),
  - the pull capacity (static working-set bound),
  - the sparse optimizer (``SparseAdagrad`` — every-step sync, paper §5),
  - a pluggable ``EmbeddingBackend`` deciding HOW rows move:
    ``GatherBackend`` (dedup + ``jnp.take``, single-device/GSPMD),
    ``RoutedBackend`` (explicit all-to-all PS routing, hash-sharded), or
    ``CachedBackend`` (device hot-row cache over a host-resident table,
    paper §2.3) — see ``repro.core.embedding_backend`` for the contract.

Every backend carries an explicit per-table STATE pytree (empty for the
stateless placements; the cache tier's id->slot map/frequency counters/
cached rows for ``cached``), created by ``init_backend_state`` and threaded
through every pull/push — it is jit-traceable and checkpointable.

Training path per batch (Algorithm 1 lines 3, 11, 13):
  1. ``pull_batch(tables, accum, states, batch)``
       -> ({name: WorkingSet}, tables, accum, states)  (one pull each;
     tables/accum come back because a cache pull may spill evicted rows)
  2. model fwd/bwd over ``ws.rows[ws.inverse]`` — grads land on the compact
     working set, not the table,
  3. ``push(tables, accum, states, working_sets, row_grads)`` — backend
     scatters the AdaGrad row updates back (or into its cache).

The pull is also exposed as an explicit *stage* (``pull_stage`` — one jitted
executable with buffer donation; ``pull_async`` dispatches it for a batch
WITHOUT blocking, ``commit`` is the documented hand-off point): because a
pull is a pure ``(tables, accum, states) -> (ws, tables, accum, states)``
transition, a prefetcher (``repro.core.prefetch.PrefetchingEngine``) can
speculatively dispatch batch t+1's pull while the device still runs batch
t's fwd/bwd — the cache tier's table spill is the only ordering point, and
it is serialized by handing the pull's returned tables to the next stage.

Serving path (co-located CTR inference, ``runtime/serve_ctr.py``): the same
engine exposes a READ-ONLY lookup next to the training pull —
``lookup``/``lookup_batch`` trace inside a caller's jit, ``lookup_stage``
is the standalone compiled stage (donating NOTHING — it must never consume
live training buffers).  A lookup serves exactly the rows a pull would
(cache-fresh values included) with zero side effects on backend state, so
an inference server can read the live trainer's tables between steps
without moving the training trajectory.  Under the DiskStore the lookup
stage reads pages through ``store.gather(serve=True)`` (serve-metered page
cache, no readahead queueing) and OVERLAYS the pending staged training
outputs read-only (``_staged_updates``) instead of absorbing them — the
store is never written on the serving path.

JAX has no native EmbeddingBag and no CSR/CSC sparse — the bag lookup here is
built from ``jnp.take`` + ``jax.ops.segment_sum`` (this IS part of the system,
per the assignment), with a Pallas TPU kernel for the fused gather-reduce hot
path in ``repro.kernels.embedding_bag``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding_backend import (  # noqa: F401  (re-exported API)
    EmbeddingBackend,
    GatherBackend,
    WorkingSet,
    make_backend,
    pull_working_set,
)
from repro.core.row_store import HostStore
from repro.core.sparse_optim import (
    SparseAdagrad,
    SparseAdagradConfig,
    SparseAdagradState,
)


# --------------------------------------------------------------------- lookup
def embedding_bag(
    table: jnp.ndarray,        # (rows, dim)
    ids: jnp.ndarray,          # (nnz,) int32 — flattened multi-hot ids
    segment_ids: jnp.ndarray,  # (nnz,) int32 — bag index of each id, sorted
    num_bags: int,
    weights: Optional[jnp.ndarray] = None,  # (nnz,) per-id weights
    combiner: str = "sum",
) -> jnp.ndarray:
    """Multi-hot bag lookup: out[b] = combine_{j: seg[j]==b} w_j * table[ids[j]]."""
    emb = jnp.take(table, ids, axis=0)  # (nnz, dim) gather
    if weights is not None:
        emb = emb * weights[:, None].astype(emb.dtype)
    out = jax.ops.segment_sum(emb, segment_ids, num_segments=num_bags)
    if combiner == "sum":
        return out
    if combiner == "mean":
        cnt = jax.ops.segment_sum(
            jnp.ones_like(segment_ids, emb.dtype), segment_ids, num_segments=num_bags
        )
        return out / jnp.maximum(cnt, 1.0)[:, None]
    if combiner == "sqrtn":
        cnt = jax.ops.segment_sum(
            jnp.ones_like(segment_ids, emb.dtype), segment_ids, num_segments=num_bags
        )
        return out / jnp.sqrt(jnp.maximum(cnt, 1.0))[:, None]
    raise ValueError(f"unknown combiner {combiner!r}")


# ---------------------------------------------------------------- the engine
@dataclasses.dataclass(frozen=True)
class TableSpec:
    """Shape + batch wiring of one embedding table.

    ``id_field`` names the batch key(s) holding this table's ids:
      - ``None``: the table name itself is the batch key,
      - a string: that batch key (any trailing shape, flattened),
      - a tuple of strings: several batch keys feeding ONE table (e.g. DIN's
        history + target item ids).  Each field is flattened per instance
        and the fields are concatenated along the per-instance axis, so the
        flat id vector stays instance-major — the trainer relies on that to
        slice the pull's inverse map into per-pod batch shards.
    ``id_col`` selects one column of the (batch, n) id tensor — the DLRM
    regime where 26 single-hot tables share one ``sparse_ids`` field.
    """

    name: str
    rows: int
    dim: int
    combiner: str = "sum"
    dtype: jnp.dtype = jnp.float32
    id_field: Optional[Union[str, Sequence[str]]] = None
    id_col: Optional[int] = None


class EmbeddingEngine:
    """Owns the tables' specs, capacity, sparse optimizer, and backend.

    ``optimizer`` may be a ``SparseAdagrad``, a ``SparseAdagradConfig``, or
    ``None`` (defaults).  ``backend`` defaults to ``GatherBackend``.

    Tables handled by the engine live in the BACKEND'S physical layout
    (``init`` prepares them; ``export`` converts back to logical rows for
    inspection/parity).  Checkpoints therefore roundtrip only through the
    same placement they were saved with.
    """

    def __init__(
        self,
        specs: Dict[str, TableSpec],
        capacity: int,
        optimizer=None,
        backend: Optional[EmbeddingBackend] = None,
        store=None,
    ):
        self.specs = dict(specs)
        self.capacity = int(capacity)
        if optimizer is None:
            optimizer = SparseAdagrad()
        elif isinstance(optimizer, SparseAdagradConfig):
            optimizer = SparseAdagrad(optimizer)
        self.opt: SparseAdagrad = optimizer
        self.backend: EmbeddingBackend = backend if backend is not None else GatherBackend()
        # the cold bottom of the hierarchy: HostStore (full jnp tables, the
        # default) or DiskStore (paged spill dir; pull/push see staged
        # working-set rows).  The backend's dataflow must match the store.
        self.store = store if store is not None else HostStore()
        staged = bool(getattr(self.backend, "staged", False))
        if self.store.kind == "disk" and not staged:
            raise ValueError(
                "DiskStore requires a staged backend (make_backend(..., "
                "staged=True)): the pull must consume working-set rows, "
                "not a resident table")
        if self.store.kind != "disk" and staged:
            raise ValueError(
                "staged backend requires store='disk': nothing stages the "
                "working-set rows under the host store")
        # per-table (uids, valid) of the batch currently staged — what the
        # gather-staged absorb needs to commit push outputs to the store
        self._staged_pending: Dict[str, Any] = {}
        self._staged_stages: Dict[bool, Any] = {}
        self._pull_jits: Dict[bool, Any] = {}   # donate flag -> jitted stage
        self._lookup_jit: Any = None            # read-only serving lookup
        self._staged_lookup: Any = None         # its DiskStore wrapper
        # id extraction runs EVERY step in front of the pull jit; compiled
        # once so per-step eager column slices don't ship their start index
        # host->device each step (id_col tables: 26 slices/step on DLRM).
        # No donation: the batch is re-read by the train stage.
        self._ids_jit = jax.jit(self._ids_from_batch_traced, donate_argnums=())

    # ------------------------------------------------------------ lifecycle
    def init(self, rng: jax.Array, scale: float = 0.01) -> Dict[str, jnp.ndarray]:
        """Random-normal logical init, converted to the backend's layout.

        Under the DiskStore the SAME per-table PRNG values are generated
        (host/disk parity is bit-exact by construction) but land in the
        store's page files; the returned "tables" are the (capacity, dim)
        staging buffers the pull/push stages thread instead.
        """
        tables = {}
        for i, (name, spec) in enumerate(sorted(self.specs.items())):
            key = jax.random.fold_in(rng, i)
            t = (
                jax.random.normal(key, (spec.rows, spec.dim), jnp.float32) * scale
            ).astype(spec.dtype)
            if self.store.kind == "disk":
                vals = np.asarray(jax.device_get(t))
                self.store.create_table(
                    name, spec.rows, spec.dim, spec.dtype,
                    init_rows_fn=lambda a, b, _v=vals: _v[a:b],
                    accum_init=self.opt.cfg.initial_accumulator,
                )
                tables[name] = jnp.zeros((self.capacity, spec.dim), spec.dtype)
            else:
                tables[name] = self.backend.prepare(t)
        return tables

    def init_state(self, tables: Dict[str, jnp.ndarray]) -> SparseAdagradState:
        return self.opt.init(tables)

    def init_backend_state(self, tables: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
        """Per-table backend state pytrees (empty tuples when stateless)."""
        return {n: self.backend.init_state(t) for n, t in tables.items()}

    def prepare(self, tables: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Logical tables -> backend layout (e.g. when init'd externally)."""
        return {n: self.backend.prepare(t) for n, t in tables.items()}

    def export(self, tables: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Backend layout -> logical rows (row i == feature id i).

        For placements with deferred writes (the cache tier), call
        ``flush`` first so dirty cached rows reach the tables."""
        return {n: self.backend.export(t) for n, t in tables.items()}

    def flush(self, tables, accum, states):
        """Force deferred backend writes (dirty cached rows) back into the
        tables/accumulator — the checkpoint/export consistency point."""
        new_tables, new_accum, new_states = {}, {}, {}
        for name in tables:
            nt, na, ns = self.backend.flush(
                tables[name], accum[name], states[name]
            )
            new_tables[name], new_accum[name], new_states[name] = nt, na, ns
        return new_tables, new_accum, new_states

    # ------------------------------------------------------------ pull/push
    def ids_from_batch(self, batch) -> Dict[str, jnp.ndarray]:
        """Extract each table's flattened id tensor from a batch dict.

        Multi-field tables (``id_field`` is a tuple) concatenate their
        fields along the per-instance axis before flattening, so the flat
        ids — and therefore the pull's inverse map — stay instance-major
        and remain sliceable into per-pod shards.  Compiled (one executable
        per batch structure): the hot path calls this every step.
        """
        return self._ids_jit(batch)

    def _ids_from_batch_traced(self, batch) -> Dict[str, jnp.ndarray]:
        out = {}
        for name, spec in self.specs.items():
            field = spec.id_field or name
            if isinstance(field, (tuple, list)):
                parts = [
                    jnp.reshape(batch[f], (batch[f].shape[0], -1))
                    for f in field
                ]
                ids = jnp.concatenate(parts, axis=1)
            else:
                ids = batch[field]
                if spec.id_col is not None:
                    ids = ids[..., spec.id_col]
            out[name] = ids.reshape(-1)
        return out

    def pull(self, tables, accum, states, flat_ids: Dict[str, jnp.ndarray]):
        """Algorithm 1 line 3: one working-set pull per table.

        Returns (working_sets, tables, accum, states) — the table tree comes
        back because a cache-tier pull may spill evicted dirty rows into it.
        """
        wss, new_tables, new_accum, new_states = {}, {}, {}, {}
        for name, ids in flat_ids.items():
            ws, nt, na, ns = self.backend.pull(
                tables[name], accum[name], states[name], ids, self.capacity
            )
            wss[name] = ws
            new_tables[name], new_accum[name], new_states[name] = nt, na, ns
        return wss, new_tables, new_accum, new_states

    def pull_batch(self, tables, accum, states, batch):
        return self.pull(tables, accum, states, self.ids_from_batch(batch))

    # ------------------------------------------------- read-only lookup path
    def lookup(self, tables, accum, states, flat_ids: Dict[str, jnp.ndarray]):
        """Read-only serving lookup: ``({name: WorkingSet}, aux)``.

        The inference counterpart of ``pull``: serves identical row values
        (the cache tier's dirty rows included — freshly trained rows are
        servable immediately) but is side-effect-free on every input, so
        interleaving lookups with training changes nothing.  ``aux`` sums
        the backends' serve meters (f32 scalars) across tables."""
        wss, aux_tot = {}, {}
        for name, ids in flat_ids.items():
            ws, aux = self.backend.lookup(
                tables[name], accum[name], states[name], ids, self.capacity
            )
            wss[name] = ws
            for k, v in aux.items():
                aux_tot[k] = aux_tot.get(k, 0.0) + v
        return wss, aux_tot

    def lookup_batch(self, tables, accum, states, batch):
        return self.lookup(tables, accum, states, self.ids_from_batch(batch))

    def lookup_stage(self):
        """The compiled LOOKUP stage: ``(tables, accum, states, flat_ids) ->
        (wss, aux)`` with NOTHING donated — the stage reads the live
        training buffers and must leave them valid for the trainer.

        Under the DiskStore the returned callable wraps the same jitted
        executable with read-only staging (``stage_lookup``): serve-metered
        page reads plus a host-side overlay of any pending staged training
        outputs, never an absorb."""
        if self._lookup_jit is None:
            def _lookup(tables, accum, states, flat_ids):
                return self.lookup(tables, accum, states, flat_ids)
            # donate_argnums=() is the contract, not an omission: a serving
            # read must never consume the trainer's live buffers
            self._lookup_jit = jax.jit(_lookup, donate_argnums=())
        if self.store.kind == "disk":
            return self._disk_lookup_stage()
        return self._lookup_jit

    # --------------------------------------------------- async pull staging
    def pull_stage(self, donate: bool = True):
        """The compiled PULL stage: ``(tables, accum, states, flat_ids) ->
        (wss, tables, accum, states)``.

        One cached ``jax.jit`` per donate flag — the SAME executable serves
        synchronous pulls and speculative prefetch dispatches, so prefetched
        training is bit-identical to synchronous training by construction.
        With ``donate=True`` the table/accumulator/state buffers are donated
        (the pull consumes the committed sparse state and hands back the
        post-pull state; callers must drop their old references).

        Under the DiskStore the returned callable wraps the SAME jitted
        executable with the host-side staging protocol (read-ahead ->
        absorb -> gather -> stage); see ``_disk_pull_stage``.
        """
        donate = bool(donate)
        if donate not in self._pull_jits:
            def _pull(tables, accum, states, flat_ids):
                return self.pull(tables, accum, states, flat_ids)
            self._pull_jits[donate] = jax.jit(
                _pull, donate_argnums=(0, 1, 2) if donate else ()
            )
        if self.store.kind == "disk":
            return self._disk_pull_stage(donate)
        return self._pull_jits[donate]

    # ----------------------------------------------- disk-store staging path
    def host_dedup(self, ids_np: np.ndarray):
        """Numpy mirror of ``_dedup``'s uid layout, run at staging time.

        Must match ``jnp.unique(size=capacity, fill_value=None)`` bit-for-
        bit: sorted ascending unique, truncated to capacity KEEPING THE
        SMALLEST, padded by repeating the minimum.  ``valid`` marks first
        occurrences (pads repeat an earlier value, so a strict > test finds
        them) — only valid positions commit back to the store, because a
        last-wins numpy scatter would let pad rows overwrite real updates.
        """
        cap = self.capacity
        u = np.unique(np.asarray(ids_np, np.int64).reshape(-1))
        k = min(len(u), cap)
        uids = np.full((cap,), u[0], np.int64)
        uids[:k] = u[:k]
        valid = np.ones((cap,), bool)
        valid[1:] = uids[1:] > uids[:-1]
        return uids, valid

    def _is_cached(self) -> bool:
        return getattr(self.backend, "cache_rows", None) is not None

    def _staged_updates(self, tables, accum, states):
        """Pending staged training outputs as ``{name: (uids, rows, accum)}``
        numpy triples — the rows the DiskStore does not hold yet.

        cached: the pull's table/accum OUTPUTS are the evicted-dirty spill
        rows, ids in ``state.spill_uid`` (-1 = no spill).  gather: the
        push's outputs are the updated staged rows of the batch recorded in
        ``_staged_pending``.  READ-ONLY: shared by ``absorb_staged`` (which
        scatters the triples into the store and clears the pending
        metadata) and the serving lookup's overlay (which patches them onto
        store reads WITHOUT committing anything).  The explicit
        ``jax.device_get`` is the deliberate d2h boundary of the disk path
        (strict-transfers-exempt); it blocks on the train step still
        holding these buffers.
        """
        out: Dict[str, Any] = {}
        if self._is_cached():
            for n in self.specs:
                got = jax.device_get({
                    "uid": states[n].spill_uid,
                    "rows": tables[n], "accum": accum[n],
                })
                m = np.asarray(got["uid"]) >= 0
                if m.any():
                    out[n] = (np.asarray(got["uid"])[m],
                              np.asarray(got["rows"])[m],
                              np.asarray(got["accum"])[m])
        else:
            for n, (uids, valid) in self._staged_pending.items():
                got = jax.device_get({"rows": tables[n], "accum": accum[n]})
                out[n] = (uids[valid],
                          np.asarray(got["rows"])[valid],
                          np.asarray(got["accum"])[valid])
        return out

    def absorb_staged(self, tables, accum, states):
        """Commit the previous step's staged outputs into the DiskStore.

        The writes are of absolute row values, so re-absorbing
        (save-then-continue, resume replay) is idempotent — which is also
        why the serving lookup may overlay the same triples read-only
        while they sit un-absorbed."""
        for n, (uids, rows, acc) in self._staged_updates(
                tables, accum, states).items():
            self.store.scatter(n, uids, rows, acc)
        self._staged_pending = {}

    def _disk_pull_stage(self, donate: bool):
        """Host staging wrapped around the jitted pull (DiskStore only).

        Order is the latency-hiding protocol: (1) the batch's dedup'd id
        stream is computed host-side (cheap numpy), (2) ``readahead``
        queues its pages for background fault-in — disk reads overlap the
        device still training the previous batch, (3) ``absorb_staged`` commits
        the previous staged outputs (this is the call that blocks on the
        train step), (4) ``gather`` finds the pages warm, (5) the rows are
        ``device_put`` and the SAME jitted pull executable dispatches.
        """
        if donate in self._staged_stages:
            return self._staged_stages[donate]
        inner = self._pull_jits[donate]

        def staged_pull(tables, accum, states, flat_ids):
            ids_np = jax.device_get(flat_ids)
            ded = {n: self.host_dedup(ids_np[n]) for n in ids_np}
            for n, (uids, valid) in ded.items():
                self.store.readahead(n, uids[valid])
            self.absorb_staged(tables, accum, states)
            staged_t, staged_a = {}, {}
            for n, (uids, _valid) in ded.items():
                rows, acc = self.store.gather(n, uids)
                staged_t[n] = jax.device_put(rows)
                staged_a[n] = jax.device_put(acc)
            self._staged_pending = ded
            return inner(staged_t, staged_a, states, flat_ids)

        self._staged_stages[donate] = staged_pull
        return staged_pull

    def stage_lookup(self, tables, accum, states, ids_np: Dict[str, np.ndarray]):
        """Read-only staging of a lookup batch's rows from the DiskStore.

        Returns ``(staged_tables, staged_accum)`` — (capacity, dim) device
        buffers in dedup'd-uid order, shaped exactly like the training
        staging buffers (same predict executable, no recompile).  Unlike
        the pull staging this NEVER writes the store: pages are read with
        ``serve=True`` (serve-metered, no readahead queueing), and any
        pending staged training outputs are OVERLAID onto the gathered rows
        host-side — the freshest values are served without absorbing the
        training side's commit, so a serving read cannot perturb the
        staging protocol.  The overlay blocks on the device buffers (an
        in-flight prefetched pull resolves here), which is the same wait
        the training absorb would pay.
        """
        overlay = self._staged_updates(tables, accum, states)
        staged_t, staged_a = {}, {}
        for n, ids in ids_np.items():
            uids, valid = self.host_dedup(ids)
            rows, acc = self.store.gather(n, uids, serve=True)
            ov = overlay.get(n)
            if ov is not None:
                o_uid, o_rows, o_acc = ov
                k = int(valid.sum())     # uids[:k] is sorted unique
                pos = np.searchsorted(uids[:k], o_uid)
                hit = pos < k
                hit[hit] = uids[pos[hit]] == o_uid[hit]
                rows[pos[hit]] = o_rows[hit].astype(rows.dtype, copy=False)
                acc[pos[hit]] = o_acc[hit]
            staged_t[n] = jax.device_put(rows)
            staged_a[n] = jax.device_put(acc)
        return staged_t, staged_a

    def _disk_lookup_stage(self):
        """Read-only staging wrapped around the jitted lookup (DiskStore)."""
        if self._staged_lookup is not None:
            return self._staged_lookup
        inner = self._lookup_jit

        def staged_lookup(tables, accum, states, flat_ids):
            ids_np = jax.device_get(flat_ids)
            staged_t, staged_a = self.stage_lookup(
                tables, accum, states, ids_np)
            return inner(staged_t, staged_a, states, flat_ids)

        self._staged_lookup = staged_lookup
        return staged_lookup

    def sync_store(self, tables, accum, states):
        """DiskStore commit point (checkpoint/export): absorb the pending
        staged outputs, write the device cache's dirty rows through, and
        persist every dirty page.  Leaves device state untouched (dirty
        bits stay set — the next sync rewrites the same values, which is
        idempotent), so it is safe at any commit boundary.  No-op under the
        host store."""
        if self.store.kind != "disk":
            return
        self.absorb_staged(tables, accum, states)
        if self._is_cached():
            for n in self.specs:
                got = jax.device_get({
                    "slot_uid": states[n].slot_uid, "dirty": states[n].dirty,
                    "rows": states[n].rows, "accum": states[n].accum,
                })
                m = np.asarray(got["dirty"]) & (np.asarray(got["slot_uid"]) >= 0)
                if m.any():
                    self.store.scatter(
                        n, np.asarray(got["slot_uid"])[m],
                        np.asarray(got["rows"])[m],
                        np.asarray(got["accum"])[m])
        self.store.flush()

    def reset_staging(self):
        """Drop pending staged-batch metadata (checkpoint resume: the
        restored pages already contain everything committed at save)."""
        self._staged_pending = {}

    def pull_async(self, tables, accum, states, batch, donate: bool = True):
        """Dispatch (do NOT block on) the pull stage for ``batch``.

        Returns the un-materialized ``(wss, tables, accum, states)`` —
        under JAX async dispatch these are futures, so the caller can keep
        queuing work (the next step's fwd/bwd) while the pull executes.
        """
        return self.pull_stage(donate)(
            tables, accum, states, self.ids_from_batch(batch)
        )

    @staticmethod
    def commit(pulled):
        """Hand a dispatched pull's ``(wss, tables, accum, states)`` to the
        train stage — the serialization point of the prefetch protocol.

        No computation happens here: the pull of batch t+1 commutes with the
        push of batch t except through the table/accum/state trees, and
        passing THESE returned trees onward is what serializes the cache
        tier's spills against the next step's reads."""
        return pulled

    def push(self, tables, accum, states, working_sets: Dict[str, WorkingSet],
             row_grads):
        """Algorithm 1 line 13: scatter row updates back (sparse optimizer
        applied by the backend — shard-locally for the routed placement,
        write-through to hot rows for the cache tier)."""
        new_tables, new_accum, new_states = {}, {}, {}
        for name, ws in working_sets.items():
            nt, na, ns = self.backend.push(
                tables[name], accum[name], states[name], ws,
                row_grads[name], self.opt
            )
            new_tables[name], new_accum[name], new_states[name] = nt, na, ns
        return new_tables, new_accum, new_states

    def cache_counters(self, states) -> Dict[str, float]:
        """Raw CUMULATIVE backend counters summed across tables: the cache
        tier's, and the packed gather tables' folds ({} for stateless
        placements).  Call outside jit — materializes the device
        scalars.  Interval (per-logging-window) deltas are the trainer's
        job: it snapshots these totals at each boundary."""
        tot: Dict[str, float] = {}
        stats_fn = getattr(self.backend, "stats", None)
        if stats_fn is not None:
            for s in states.values():
                for k, v in stats_fn(s).items():
                    tot[k] = tot.get(k, 0.0) + v
        # the store's page-cache/disk meters ride the same counter protocol
        # (cumulative floats; the trainer's logger diffs them per interval)
        for k, v in self.store.stats().items():
            tot[k] = tot.get(k, 0.0) + float(v)
        return tot

    @staticmethod
    def derive_cache_stats(counters: Dict[str, float]) -> Dict[str, float]:
        """Counter totals/deltas -> the reported stat dict ({} for {}).

        An interval with zero lookups (idle / predict-only window) reports
        ``cache_hit_rate = 0.0`` — not the fake perfect 1.0 that
        ``1 - 0/max(0, 1)`` would produce in fit history.  Under the
        DiskStore the page-tier meters ride along (``page_hit_rate``,
        ``disk_bytes_read``/``disk_bytes_written``, ``pages_evicted``) —
        the third level of the hierarchy.  Packed gather tables report
        ``packed_group_share``: the share of pushed distinct rows whose
        write was folded into another's (same physical row)."""
        if not counters:
            return {}
        out: Dict[str, float] = {}
        if "lookups" in counters:
            lookups = counters["lookups"]
            hit_rate = (
                0.0 if lookups <= 0.0 else 1.0 - counters["fetched"] / lookups
            )
            out.update({
                "cache_hit_rate": hit_rate,
                "evictions": int(counters["evictions"]),
                "cache_bytes_h2d": counters["bytes_h2d"],
                "cache_bytes_d2h": counters["bytes_d2h"],
            })
        if "page_hits" in counters:
            touches = counters["page_hits"] + counters["page_misses"]
            out.update({
                "page_hit_rate": (
                    0.0 if touches <= 0.0 else counters["page_hits"] / touches
                ),
                "pages_evicted": int(counters["pages_evicted"]),
                "disk_bytes_read": counters["disk_bytes_read"],
                "disk_bytes_written": counters["disk_bytes_written"],
            })
        if "packed_pushed" in counters:
            pushed = counters["packed_pushed"]
            out["packed_group_share"] = (
                0.0 if pushed <= 0.0 else counters["packed_merged"] / pushed)
        return out

    def cache_stats(self, states) -> Dict[str, float]:
        """Whole-run cache stats ({} for stateless placements)."""
        return self.derive_cache_stats(self.cache_counters(states))

    @staticmethod
    def overflow(working_sets: Dict[str, WorkingSet]) -> jnp.ndarray:
        """Total dropped (unserved) requests this batch — the PS overload
        counter production monitoring watches."""
        return sum(ws.n_dropped for ws in working_sets.values())

    # -------------------------------------------------------------- lookups
    @staticmethod
    def bag_from_working(
        working: jnp.ndarray,      # (capacity, dim) pulled rows
        inverse: jnp.ndarray,      # (nnz,) id slot -> working row
        segment_ids: jnp.ndarray,  # (nnz,) id slot -> bag
        num_bags: int,
        weights: Optional[jnp.ndarray] = None,
        combiner: str = "sum",
        fused: bool = False,
    ) -> jnp.ndarray:
        """Bag lookup routed through the pulled working set (differentiable in
        ``working`` — its gradient is exactly the row_grads to scatter back).

        ``fused=True`` runs the gather+bag as ONE Pallas kernel pass over the
        working set (``kernels.ops.embedding_bag_working``); both branches
        share the same reference expression for the gradient.  The forward
        is bit-identical to the unfused one in interpret mode and equal up
        to f32 reassociation on TPU.
        """
        from repro.kernels import ops, ref

        if fused:
            return ops.embedding_bag_working(
                working, inverse, segment_ids, weights, num_bags, combiner
            )
        return ref.embedding_bag_combiner_ref(
            working, inverse, segment_ids, weights, num_bags, combiner
        )

    def memory_bytes(self) -> int:
        return sum(
            s.rows * s.dim * jnp.dtype(s.dtype).itemsize for s in self.specs.values()
        )
