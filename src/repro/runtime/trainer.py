"""Training runtimes.

``DenseTrainer`` — any model whose parameters are all dense (LM, GNN):
podded replicas + k-step Adam; per-pod batches; static local/merge
executables; checkpoint/restart; optional delayed (asynchronous) merge
application (``merge_delay``).

``HybridTrainer`` — the paper's CTR/recsys regime: dense tower under k-step
Adam + giant sparse tables owned by an ``EmbeddingEngine``.  Algorithm 1's
pull -> train -> push runs as TWO compiled stages behind a pluggable
``EmbeddingBackend``: a PULL stage (dedup + gather/route/cache admission)
and a TRAIN+PUSH stage (fwd/bwd on the working set, k-step Adam, row-update
scatter).  The split is what enables the paper's Fig. 5 pipeline: with
``TrainerConfig.prefetch`` the trainer dispatches batch t+1's pull right
after batch t's train stage is queued (``repro.core.prefetch``), so under
JAX async dispatch the pull overlaps the step still executing — and the
hand-off of the pull's returned ``(tables, accum, state)`` trees serializes
the cache tier's spills, keeping prefetched training bit-identical to
synchronous training.  Checkpoints are only written at commit boundaries
(never with a pull in flight); ``save`` enforces this loudly.

The hot path never blocks the host: ``train_step`` returns the loss as a
device array and accumulates the overflow counter on-device; Python floats
materialize only at ``log_every``/checkpoint boundaries (``fit`` history
values are plain floats as before).  ``sparse_metrics`` reports PER-INTERVAL
deltas (since the previous logging boundary) with whole-run cumulative
values under ``*_total`` keys.

Construct trainers directly, or — config-driven — through
``repro.runtime.factory.build_trainer(arch_name, TrainerConfig)``, which
wires models, engines, and placements from the ``repro.configs`` registry.

Both runtimes implement the fault-tolerance contract:
- crash-consistent checkpoints (atomic dirs) at a configurable cadence,
  including the int8 error-feedback residual when ``merge="int8_ef"``,
- ``resume()`` picks up the newest complete checkpoint (mesh-independent),
- the k-step merge is the only cross-pod sync point,
- ``merge_delay > 0`` (DenseTrainer) applies each merge's cross-pod average
  ``merge_delay`` boundaries late, preserving the local drift since its
  snapshot (DCN latency hiding; the in-flight merge queue is not
  checkpointed — a restart resumes with an empty queue).

Config knobs are never silently ignored: a trainer that cannot honor
``prefetch``/``merge_delay``/``merge_quorum`` raises at construction.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import shutil
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step, read_manifest
from repro.core.embedding_backend import row_packing
from repro.core.embedding_engine import EmbeddingEngine
from repro.core.kstep import KStepAdam, KStepConfig, pod_replicate, pod_slice
from repro.core.prefetch import PrefetchingEngine
from repro.core.sparse_optim import SparseAdagradConfig
from repro.kernels import ops
from repro.runtime import spans

Pytree = Any


@dataclasses.dataclass
class TrainerConfig:
    n_pod: int = 1
    kstep: KStepConfig = dataclasses.field(default_factory=KStepConfig)
    sparse: SparseAdagradConfig = dataclasses.field(default_factory=SparseAdagradConfig)
    placement: str = "gather"     # sparse backend: "gather"|"routed"|"cached"
    capacity: Optional[int] = None  # working-set bound (None: arch default)
    cache_rows: Optional[int] = None  # device cache size for "cached"
                                      # (None: arch default; must be >= capacity)
    prefetch: bool = False        # double-buffered pull prefetch
                                  # (HybridTrainer only; Fig. 5 overlap)
    fused_kernels: Optional[bool] = None  # fused Pallas sparse pull/push +
                                          # bag (HybridTrainer only).  None =
                                          # auto: on for a real TPU backend,
                                          # off elsewhere (ops.resolve_fused)
    store: str = "host"           # cold tier: "host" (resident tables) |
                                  # "disk" (paged spill dir; HybridTrainer)
    spill_dir: Optional[str] = None   # page directory (required for "disk")
    page_rows: Optional[int] = None   # rows per page file (None: 1024)
    page_cache_pages: Optional[int] = None  # RAM page-cache capacity
                                            # (None: unbounded full mirror)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 200
    ckpt_keep: int = 3
    ckpt_async: bool = True
    merge_quorum: float = 1.0     # reserved: only 1.0 (all pods) implemented
    merge_delay: int = 0          # async merge application lag, in merges
                                  # (DenseTrainer only)
    log_every: int = 50
    donate: bool = True


def _reject_dead_knobs(cfg: TrainerConfig, trainer: str, merge_delay_ok: bool):
    """No-silent-config contract: a documented knob either works or raises —
    it is never accepted and ignored."""
    if cfg.merge_quorum != 1.0:
        raise NotImplementedError(
            f"{trainer}: merge_quorum={cfg.merge_quorum} is not implemented "
            "(there is no straggler/failure detector yet — merges always "
            "run over all pods); set merge_quorum=1.0"
        )
    if cfg.merge_delay < 0:
        raise ValueError(f"merge_delay must be >= 0, got {cfg.merge_delay}")
    if cfg.merge_delay > 0 and not merge_delay_ok:
        raise ValueError(
            f"{trainer} does not support merge_delay={cfg.merge_delay}: the "
            "sparse side synchronizes every step, so a delayed dense merge "
            "would shear the two halves of the model — use DenseTrainer, or "
            "merge_delay=0"
        )


def next_pow2(n) -> int:
    """Smallest power of two >= n (powers of two keep routed shard
    divisibility — shared by capacity defaults and autoscaling)."""
    cap = 1
    while cap < n:
        cap <<= 1
    return cap


def pod_batch(batch: Dict[str, np.ndarray], n_pod: int) -> Dict[str, jnp.ndarray]:
    """Split a global batch into per-pod shards (leading pod dim).

    Host batches are staged with EXPLICIT ``jax.device_put`` (a no-op for
    already-device leaves) so the loop survives
    ``jax.transfer_guard("disallow")`` — the strict-transfers contract:
    every host->device crossing in the hot path is deliberate."""
    def f(x):
        x = jax.device_put(x)
        return x.reshape((n_pod, x.shape[0] // n_pod) + x.shape[1:])
    return jax.tree.map(f, batch)


def _drop_ef_if_absent(like: dict, ckpt: CheckpointManager) -> dict:
    """Restoring with merge="int8_ef" must tolerate checkpoints written
    without the residual (older runs, or runs under a lossless merge): drop
    'ef' from the restore template when the newest manifest lacks it, so
    resume keeps the fresh zero residual instead of raising KeyError."""
    if "ef" not in like:
        return like
    step = latest_step(ckpt.directory)
    man = read_manifest(ckpt.directory, step) if step is not None else None
    if man is not None and not any(
        k.split("/")[0] == "ef" for k in man["leaves"]
    ):
        like = dict(like)
        like.pop("ef")
    return like


def history_record(trainer, loss, t0: float) -> dict:
    """One fit-history record at a logging boundary — the single copy of
    the record schema shared by ``fit`` and ``repro.runtime.online``:
    step/loss/sec plus the trainer's PER-INTERVAL sparse metrics
    (``advance=True``: recording moves the interval baseline forward)."""
    rec = {"step": trainer.step_num, "loss": float(jax.device_get(loss)),
           "sec": time.perf_counter() - t0}
    sparse_metrics = getattr(trainer, "sparse_metrics", None)
    if sparse_metrics is not None:
        rec.update(sparse_metrics(advance=True))
    return rec


def _fit_loop(trainer, batches: Iterator, steps: int, eval_fn=None) -> list:
    """Shared fit(): train ``steps`` batches, log every ``log_every``.

    Runs one batch ahead of the device: the next batch is drawn from the
    iterator while the step executes, and — when the trainer prefetches
    (``cfg.prefetch``) — its pull is dispatched as soon as the current step
    is queued.  Checkpoints (inside ``train_step``) and logged metrics both
    materialize BEFORE the next pull is dispatched, so they capture the
    committed state, never a speculative pull."""
    if steps <= 0:
        if trainer.ckpt:
            trainer.ckpt.wait()   # fit(gen, 0) still flushes async saves
        return trainer.history
    t0 = time.perf_counter()
    prefetch = getattr(trainer, "prefetch", None)
    b = next(batches)
    if prefetch is not None:
        prefetch(b)
    for i in range(steps):
        loss = trainer.train_step(b)
        b = next(batches) if i + 1 < steps else None
        if trainer.step_num % trainer.cfg.log_every == 0:
            # sparse-path health (per-interval overflow + cache hit rate/
            # evictions) rides along; only the logger moves the baseline.
            rec = history_record(trainer, loss, t0)
            if eval_fn:
                rec["eval"] = eval_fn(trainer)
            trainer.history.append(rec)
        if prefetch is not None and b is not None:
            prefetch(b)
    if trainer.ckpt:
        trainer.ckpt.wait()
    return trainer.history


class DenseTrainer:
    """All-dense models: k-step Adam over podded replicas."""

    def __init__(
        self,
        loss_fn: Callable[[Pytree, Dict], jnp.ndarray],
        params: Pytree,
        cfg: TrainerConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        param_shardings: Optional[Pytree] = None,
    ):
        self.cfg = cfg
        _reject_dead_knobs(cfg, "DenseTrainer", merge_delay_ok=True)
        if cfg.prefetch:
            raise ValueError(
                "DenseTrainer: prefetch=True is a sparse-path feature "
                "(HybridTrainer's pull prefetch) — an all-dense model has "
                "no pull stage to overlap; set prefetch=False"
            )
        if cfg.fused_kernels:
            raise ValueError(
                "DenseTrainer: fused_kernels=True is a sparse-path feature "
                "(the fused embedding pull/push kernels) — an all-dense "
                "model has no working set to fuse over; leave "
                "fused_kernels=None"
            )
        if (cfg.store != "host" or cfg.spill_dir is not None
                or cfg.page_rows is not None
                or cfg.page_cache_pages is not None):
            raise ValueError(
                "DenseTrainer: store/spill_dir/page_rows/page_cache_pages "
                "are sparse-path knobs (the embedding tables' storage "
                "hierarchy) — an all-dense model has no tables to spill"
            )
        if cfg.merge_delay > 0 and cfg.kstep.merge == "int8_ef":
            raise NotImplementedError(
                "merge_delay>0 with merge='int8_ef' is not supported: the "
                "error-feedback residual needs the fused merge path"
            )
        self.n_pod = cfg.n_pod
        self.mesh = mesh
        self.params = pod_replicate(params, cfg.n_pod)
        if param_shardings is not None:
            self.params = jax.tree.map(jax.device_put, self.params, param_shardings)
        self.opt = KStepAdam(cfg.kstep, cfg.n_pod, mesh=mesh)
        self.opt_state = self.opt.init(self.params)
        self.step_num = 0
        self.ckpt = (
            CheckpointManager(cfg.ckpt_dir, cfg.ckpt_keep, cfg.ckpt_every, cfg.ckpt_async)
            if cfg.ckpt_dir else None
        )
        self._loss_fn = loss_fn
        donate = (0, 2) if cfg.donate else ()
        self._local = jax.jit(self._make_step(merge=False), donate_argnums=donate)
        self._merge = jax.jit(self._make_step(merge=True), donate_argnums=donate)
        # merge_delay > 0: queue of (snapshot, in-flight merged average)
        self._pending_merges: collections.deque = collections.deque()
        if cfg.merge_delay > 0:
            # donation decisions (undonated-hot-jit contract): the collective
            # keeps params alive (snapshot + local steps still read them) but
            # consumes the opt_state it replaces; the delayed apply consumes
            # all three — params are reassigned from its output, and the
            # snapshot/merged pair is popped from the queue (snapshot is a
            # real copy, so no donate-twice aliasing with params).
            self._delayed_collective = jax.jit(
                self.opt.delayed_merge_collective, donate_argnums=(1,)
            )
            self._delayed_apply = jax.jit(
                KStepAdam.apply_delayed_merge, donate_argnums=(0, 1, 2)
            )
        self.history: list = []

    def _make_step(self, merge: bool):
        def step(params, batch_podded, opt_state):
            def total_loss(p):
                losses = jax.vmap(lambda pi, bi: self._loss_fn(pi, bi))(p, batch_podded)
                return jnp.sum(losses), losses
            grads, losses = jax.grad(total_loss, has_aux=True)(params)
            new_p, new_s = self.opt.step(params, grads, opt_state, merge=merge)
            return new_p, new_s, jnp.mean(losses)
        return step

    def pod_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        return pod_batch(batch, self.n_pod)

    def _delayed_merge_boundary(self):
        """``merge_delay > 0``: at each merge boundary, first apply the
        average launched ``merge_delay`` boundaries ago (preserving the
        local drift since its snapshot — ``KStepAdam.apply_delayed_merge``),
        then launch this boundary's cross-pod collective (parameter average
        + the Algorithm-2 ``v_hat <- mean v_local`` refresh, which applies
        immediately so local denominators stay fresh)."""
        if len(self._pending_merges) >= self.cfg.merge_delay:
            snap_old, merged_old = self._pending_merges.popleft()
            self.params = self._delayed_apply(self.params, snap_old, merged_old)
        snap = KStepAdam.snapshot(self.params)
        merged, self.opt_state = self._delayed_collective(
            self.params, self.opt_state
        )
        self._pending_merges.append((snap, merged))

    def train_step(self, batch, podded: bool = False) -> jnp.ndarray:
        """``podded=True``: batch leaves already carry the leading pod dim
        (e.g. full-graph training where each pod sees the same graph).

        Returns the mean loss as a DEVICE array (no host sync — the hot
        path never blocks; ``float()`` it at logging boundaries)."""
        self.step_num += 1
        is_boundary = (self.step_num % self.cfg.kstep.k) == 0
        fused_merge = is_boundary and self.cfg.merge_delay == 0
        fn = self._merge if fused_merge else self._local
        pb = jax.tree.map(jnp.asarray, batch) if podded else self.pod_batch(batch)
        self.params, self.opt_state, loss = fn(self.params, pb, self.opt_state)
        if is_boundary and self.cfg.merge_delay > 0:
            self._delayed_merge_boundary()
        if self.ckpt and self.ckpt.should_save(self.step_num):
            self.save()
        return loss

    # ----------------------------------------------------- fault tolerance
    def _ckpt_tree(self):
        tree = {"params": self.params, "m": self.opt_state.m,
                "v_local": self.opt_state.v_local, "v_hat": self.opt_state.v_hat}
        if self.opt_state.ef is not None:
            # int8_ef merge: the error-feedback residual is state — dropping
            # it on restart silently re-zeros the compensation.
            tree["ef"] = self.opt_state.ef
        return tree

    def save(self):
        # checkpointing deliberately materializes device state host-side —
        # an allow-listed section under strict-transfers runs
        with jax.transfer_guard("allow"):
            self.ckpt.save(
                self.step_num, self._ckpt_tree(),
                meta={"n_pod": self.n_pod, "k": self.cfg.kstep.k},
            )

    def resume(self) -> bool:
        if not self.ckpt:
            return False
        like = _drop_ef_if_absent(self._ckpt_tree(), self.ckpt)
        step, tree = self.ckpt.restore_latest(like)
        if step is None:
            return False
        self.step_num = step
        self.params = tree["params"]
        self.opt_state = self.opt_state._replace(
            step=jnp.asarray(step, jnp.int32), m=tree["m"],
            v_local=tree["v_local"], v_hat=tree["v_hat"],
            ef=tree.get("ef", self.opt_state.ef),
        )
        self._pending_merges.clear()   # in-flight delayed merges don't resume
        return True

    def fit(self, batches: Iterator, steps: int, eval_fn=None) -> list:
        return _fit_loop(self, batches, steps, eval_fn)


class HybridTrainer:
    """Dense tower (k-step Adam, podded) + sparse tables behind an
    ``EmbeddingEngine`` — the paper's production regime.

    Parameters
    ----------
    dense_params: the dense tower's parameter pytree (un-podded).
    engine: owns TableSpecs, capacity, the sparse optimizer, and the
        placement backend; the trainer never touches raw tables directly.
    embed_fn(workings, invs, batch): build model inputs from pulled rows
        (``workings[name]`` = ``WorkingSet.rows``, ``invs[name]`` = the
        inverse map restricted to this pod's batch shard).
    loss_fn(dense, emb, batch, predict=False): dense-side loss given
        embeddings (``predict=True`` returns scores).
    tables: optional pre-initialized tables IN THE BACKEND'S LAYOUT
        (e.g. from ``engine.init`` or ``engine.prepare``); ``None`` lets the
        engine initialize them from ``rng``.

    The train step runs as two compiled stages sharing one contract —
    ``pull`` (``engine.pull_stage``) and ``train+push`` — so the synchronous
    path and the prefetched path (``cfg.prefetch``; see
    ``repro.core.prefetch``) execute the SAME executables and produce
    bit-identical results; the prefetched path merely dispatches the pull of
    batch t+1 before batch t's train stage has finished executing.
    """

    def __init__(
        self,
        dense_params: Pytree,
        engine: EmbeddingEngine,
        embed_fn: Callable,
        loss_fn: Callable,
        cfg: TrainerConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        tables: Optional[Dict[str, jnp.ndarray]] = None,
        rng: Optional[jax.Array] = None,
    ):
        self.cfg = cfg
        _reject_dead_knobs(cfg, "HybridTrainer", merge_delay_ok=False)
        self.n_pod = cfg.n_pod
        self.mesh = mesh
        self.engine = engine
        self.dense = pod_replicate(dense_params, cfg.n_pod)
        self.tables = (
            tables if tables is not None
            else engine.init(rng if rng is not None else jax.random.key(0))
        )
        self.opt = KStepAdam(cfg.kstep, cfg.n_pod, mesh=mesh)
        self.opt_state = self.opt.init(self.dense)
        self.sparse_state = engine.init_state(self.tables)
        # per-table backend state (cache-tier id->slot map/counters/rows;
        # empty tuples for the stateless placements) — threaded through the
        # compiled stages and checkpointed alongside the tables.
        self.backend_state = engine.init_backend_state(self.tables)
        self.step_num = 0
        # device-resident cumulative overflow counter (materialized only at
        # logging/checkpoint boundaries — the hot path never syncs the host)
        self._overflow = jnp.zeros((), jnp.int32)
        self._commit_to_mesh()
        self._metrics_prev: Dict[str, float] = {}  # counter snapshot at last log
        self._metrics_base_step = 0   # step the counters were last re-zeroed at
        self._embed = embed_fn
        self._loss = loss_fn
        # the checkpoint GC doubles as the spill-dir wreckage sweeper when
        # the engine's tables live in a DiskStore
        self.ckpt = (
            CheckpointManager(
                cfg.ckpt_dir, cfg.ckpt_keep, cfg.ckpt_every, cfg.ckpt_async,
                spill_dir=getattr(engine.store, "spill_dir", None),
            )
            if cfg.ckpt_dir else None
        )
        donate = cfg.donate
        # stage 1: the engine's compiled pull (shared with the prefetcher —
        # same executable => prefetched training is bit-identical)
        self._pull = engine.pull_stage(donate=donate)
        # stage 2: fwd/bwd on the working set + k-step Adam + push.  The
        # working sets (arg 4) are NOT donated: their int index buffers and
        # capacity-shaped rows can never alias the stage's outputs.
        # Stages over a multi-device mesh trace on it, so the Pallas
        # kernels inside run whole on each device.
        train_donate = (0, 1, 2, 3, 6, 7) if donate else ()
        on_mesh = functools.partial(ops.traced_on, self._state_mesh())
        self._train_local = jax.jit(
            on_mesh(self._make_train(False)), donate_argnums=train_donate
        )
        self._train_merge = jax.jit(
            on_mesh(self._make_train(True)), donate_argnums=train_donate
        )
        self._prefetcher = (
            PrefetchingEngine(engine, donate=donate) if cfg.prefetch else None
        )
        # inference path: READ-ONLY lookup + embed + score compiled as one
        # stage so the per-request loop dispatches a single executable (an
        # eager pull ships scalar operands host->device on every call).
        # Nothing is donated — predict must not consume the committed
        # training state (the engine's lookup contract guarantees it also
        # mutates none of it).
        self._predict_jit = jax.jit(on_mesh(self._predict_traced),
                                    donate_argnums=())
        # serving-side meters, accumulated host-side per predict — kept
        # fully separate from the training-interval cache stats so
        # interleaved serving never moves sparse_metrics (see
        # ``serve_metrics``)
        self._serve_counters: Dict[str, float] = {}
        # cumulative host->device bytes of the batches ``_stage`` ships
        # (the host leaves' sizes; nothing on the device is read)
        self.staged_bytes = 0
        self.history: list = []

    def _make_train(self, merge: bool):
        def train(dense, tables, accum, bstate, wss, batch_podded, opt_state,
                  overflow):
            workings = {n: ws.rows for n, ws in wss.items()}
            # inverse indices sliced per pod so each replica embeds only its
            # own batch shard (vmapped leading pod dim)
            invs_podded = {
                n: ws.inverse.reshape(self.n_pod, -1) for n, ws in wss.items()
            }

            # ---- local fwd/bwd on the working set (Algorithm 1 line 12)
            def total_loss(dense_p, w):
                def per_pod(dp, bp, inv_p):
                    emb = self._embed(w, inv_p, bp)
                    return self._loss(dp, emb, bp)
                losses = jax.vmap(per_pod, in_axes=(0, 0, 0))(
                    dense_p, batch_podded, invs_podded
                )
                return jnp.sum(losses), losses

            (dense_g, work_g), losses = jax.grad(total_loss, argnums=(0, 1), has_aux=True)(
                dense, workings
            )
            # sparse grads are summed over pods by autodiff; average them
            # (paper: sparse side synchronized every iteration).
            work_g = jax.tree.map(lambda g: g / self.n_pod, work_g)

            # ---- dense k-step Adam
            new_dense, new_opt = self.opt.step(dense, dense_g, opt_state, merge=merge)

            # ---- PUSH (line 13): backend scatters/routes the row updates.
            new_tables, new_accum, bstate = self.engine.push(
                tables, accum, bstate, wss, work_g
            )
            new_overflow = overflow + self.engine.overflow(wss).astype(jnp.int32)
            return (new_dense, new_tables, new_accum, bstate, new_opt,
                    jnp.mean(losses), new_overflow)

        return train

    def pod_batch(self, batch):
        return pod_batch(batch, self.n_pod)

    def _state_mesh(self):
        """The mesh the trainer state lives on: the trainer's own, else the
        backend's (``RoutedBackend`` builds one), else None."""
        return self.mesh if self.mesh is not None else getattr(
            self.engine.backend, "mesh", None)

    def _commit_to_mesh(self):
        """Commit the trainer state to the mesh's replicated sharding.

        Mesh-backed steps (routed placement) emit every state leaf with
        ``NamedSharding(mesh, P())``; eagerly-initialized (or freshly
        restored) state is uncommitted ``SingleDeviceSharding``, so without
        this the FIRST train executable is compiled for a signature no later
        step ever uses again — a full silent double-compile of the largest
        jit (caught by the trace audit's retrace check).

        The backend's internal mesh counts too: ``RoutedBackend`` builds one
        when none is passed, and its shard_maps stamp that mesh's sharding
        on every output flowing through the train jit."""
        mesh = self._state_mesh()
        if mesh is None:
            return
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        put = lambda tree: jax.device_put(tree, rep)
        self.dense = put(self.dense)
        self.tables = put(self.tables)
        self.opt_state = put(self.opt_state)
        self.sparse_state = put(self.sparse_state)
        self.backend_state = put(self.backend_state)
        self._overflow = put(self._overflow)

    def _stage(self, batch):
        # explicit h2d staging: jax.device_put is transfer-guard-exempt
        # (deliberate), where jnp.asarray would count as an implicit sync
        self.staged_bytes += sum(x.nbytes for x in jax.tree.leaves(batch)
                                 if isinstance(x, np.ndarray))
        return jax.device_put(batch)

    def prefetch(self, batch) -> bool:
        """Speculatively dispatch ``batch``'s working-set pull (the Fig. 5
        overlap).  No-op unless ``cfg.prefetch``; idempotent for the batch
        already in flight; a DIFFERENT batch while one is pending is a
        pipeline bug and raises.  After dispatch the trainer's sparse-state
        handles point at the pull's pass-through trees (logically identical
        values — a pull moves rows coherently, only push changes them), so
        online ``predict`` keeps working mid-flight."""
        if self._prefetcher is None or batch is None:
            return False
        pending = self._prefetcher.pending
        if pending is not None:
            if pending.src is batch:
                return True
            raise RuntimeError(
                "HybridTrainer.prefetch: a pull for a different batch is "
                "already in flight — train_step() it before prefetching "
                "the next batch (the pipeline is one batch deep)"
            )
        pending = self._prefetcher.dispatch(
            self.tables, self.sparse_state.accum, self.backend_state,
            self._stage(batch), src=batch,
        )
        # the dispatch donated the committed buffers; the post-pull trees
        # are now the only valid handles until the commit in train_step
        self.tables = pending.tables
        self.backend_state = pending.bstate
        self.sparse_state = self.sparse_state._replace(accum=pending.accum)
        return True

    def train_step(self, batch) -> jnp.ndarray:
        """One pull -> train -> push step on ``batch``.

        Uses the prefetched pull when one is in flight (``cfg.prefetch``),
        otherwise dispatches the pull stage synchronously — the same
        executables either way.  Returns the mean loss as a DEVICE array
        (no host sync; ``float()`` it at logging boundaries)."""
        if self._prefetcher is not None:
            pending = self._prefetcher.pending
            # reject BEFORE any state moves (step_num included): a caught
            # misuse error must not shift the merge/checkpoint cadence
            if pending is not None and pending.src is not batch:
                raise RuntimeError(
                    "HybridTrainer.train_step: the in-flight prefetched pull "
                    "belongs to a different batch than the one passed — "
                    "feed the same batch to prefetch() and train_step()"
                )
        self.step_num += 1
        is_merge = (self.step_num % self.cfg.kstep.k) == 0
        fn = self._train_merge if is_merge else self._train_local
        if self._prefetcher is not None:
            with spans.span("repro.train.pull"):
                if self._prefetcher.pending is None:
                    self.prefetch(batch)   # cold start: pull now (not early)
                p = self._prefetcher.commit()
            wss, staged = p.wss, p.batch
            tables, accum, bstate = p.tables, p.accum, p.bstate
        else:
            with spans.span("repro.train.stage"):
                staged = self._stage(batch)
            with spans.span("repro.train.ids"):
                ids = self.engine.ids_from_batch(staged)
            with spans.span("repro.train.pull"):
                wss, tables, accum, bstate = self.engine.commit(self._pull(
                    self.tables, self.sparse_state.accum, self.backend_state,
                    ids,
                ))
        with spans.span("repro.train.pod_batch"):
            podded = self.pod_batch(staged)
        with spans.span("repro.train.launch_merge" if is_merge
                        else "repro.train.launch"):
            (self.dense, self.tables, accum, self.backend_state,
             self.opt_state, loss, self._overflow) = fn(
                self.dense, tables, accum, bstate, wss,
                podded, self.opt_state, self._overflow,
            )
        self.sparse_state = self.sparse_state._replace(accum=accum)
        if self.ckpt and self.ckpt.should_save(self.step_num):
            self.save()   # committed state: the next pull is not yet queued
        return loss

    def train_step_prefetched(self, batch, next_batch=None) -> jnp.ndarray:
        """One pipelined step for manual (non-``fit``) loops: train on
        ``batch`` (consuming its prefetched pull, or pulling now on a cold
        start), then dispatch ``next_batch``'s pull so it overlaps the step
        just queued."""
        loss = self.train_step(batch)
        if next_batch is not None:
            self.prefetch(next_batch)
        return loss

    @property
    def overflow_dropped(self) -> int:
        """Cumulative unserved pull/push requests, across restarts (the
        counter is checkpointed) — materializes the device-resident scalar
        (read at logging boundaries, not per step; explicit device_get keeps
        strict-transfers runs clean)."""
        return int(jax.device_get(self._overflow))

    def predict(self, batch) -> np.ndarray:
        """Inference with pod-0's dense replica (online predict-then-train,
        and the executable the co-located CTR server drives).

        Runs on the engine's READ-ONLY lookup contract: the sparse rows are
        served exactly as a pull would serve them (cache-fresh values
        included — a row trained at step t is servable immediately) but
        NOTHING mutates — no cache admission/eviction, no counter writes,
        no disk absorb — so any interleaving of predicts leaves the
        training trajectory and the training-interval stats bit-identical.
        Valid while a prefetched pull is in flight: the pass-through trees
        it reads are logically identical to the committed state."""
        if self.engine.store.kind == "disk":
            return self._predict_disk(batch)
        with spans.span("repro.predict.stage"):
            batch = self._stage(batch)
        with spans.span("repro.predict.launch"):
            scores, aux = self._predict_jit(
                self.dense, self.tables, self.sparse_state.accum,
                self.backend_state, batch,
            )
        return self._finish_predict(scores, aux)

    def _predict_disk(self, batch) -> np.ndarray:
        """Disk-store inference: stage THIS batch's rows, read-only.

        The training staging buffers hold another batch's rows, so predict
        builds its own through ``engine.stage_lookup``: host-dedup the
        batch's ids, serve-metered ``store.gather``, then OVERLAY any
        pending staged training outputs onto the gathered rows host-side —
        the freshest values are served without absorbing (writing) anything
        into the store, and the same ``_predict_jit`` runs over them (the
        staged shapes match the training buffers, so no recompile).  The
        overlay replaces the old absorb-before-predict: it is exact in
        every pipeline state (un-absorbed push outputs are patched to their
        post-absorb values; a pending prefetched pull's pass-through rows
        patch idempotently; in-flight cache spills patch to the values the
        next absorb will commit)."""
        batch = self._stage(batch)
        ids_np = {
            n: np.asarray(jax.device_get(ids))
            for n, ids in self.engine.ids_from_batch(batch).items()
        }
        staged_t, staged_a = self.engine.stage_lookup(
            self.tables, self.sparse_state.accum, self.backend_state, ids_np
        )
        scores, aux = self._predict_jit(
            self.dense, staged_t, staged_a, self.backend_state, batch,
        )
        return self._finish_predict(scores, aux)

    def _finish_predict(self, scores, aux) -> np.ndarray:
        # scores are consumed host-side (streaming AUC / response writing):
        # ONE explicit d2h materializes them together with the lookup's
        # serve meters, which accumulate into the serve-side counters
        with spans.span("repro.predict.fetch"):
            got = jax.device_get({"scores": scores, "aux": aux})
        c = self._serve_counters
        c["serve_requests"] = c.get("serve_requests", 0.0) + float(
            np.asarray(got["scores"]).shape[0])
        for k, v in got["aux"].items():
            c[k] = c.get(k, 0.0) + float(v)
        return np.asarray(got["scores"])

    def _predict_traced(self, dense, tables, accum, bstate, batch):
        dense0 = pod_slice(dense, 0)
        wss, aux = self.engine.lookup_batch(tables, accum, bstate, batch)
        workings = {n: ws.rows for n, ws in wss.items()}
        invs = {n: ws.inverse for n, ws in wss.items()}
        emb = self._embed(workings, invs, batch)
        return self._loss(dense0, emb, batch, predict=True), aux

    def serve_metrics(self) -> Dict[str, float]:
        """Cumulative SERVING-side counters — the monitoring surface of the
        co-located inference tier, fully separate from ``sparse_metrics``
        (whose training-interval stats never count inference traffic):
        ``serve_requests`` (instances scored), ``serve_lookups`` (id slots
        served), and under the cache tier ``serve_misses`` +
        ``serve_hit_rate`` (same ``1 - misses/lookups`` convention as
        training).  DiskStore page meters for serving reads ride along
        under ``serve_page_*``/``serve_disk_*`` keys."""
        m = dict(self._serve_counters)
        if "serve_misses" in m:
            lk = m.get("serve_lookups", 0.0)
            m["serve_hit_rate"] = (
                0.0 if lk <= 0.0 else 1.0 - m["serve_misses"] / lk)
        for k, v in self.engine.store.serve_stats().items():
            m[f"serve_{k}"] = float(v)
        return m

    def sparse_metrics(self, advance: bool = False) -> Dict[str, float]:
        """Sparse-path health for trainer history/monitoring, PER INTERVAL
        (deltas since the last logging boundary — the current window):
        ``overflow_dropped`` plus, under the cached placement,
        ``cache_hit_rate``/``evictions``/host<->device byte meters, and
        for packed gather tables ``packed_group_share``.
        Whole-run cumulative values ride along under ``*_total`` keys
        (``cache_hit_rate_total`` is the whole-run blend).

        A PURE read by default — poll it freely between boundaries.  Only
        ``advance=True`` (what ``fit``'s logger passes) moves the interval
        baseline forward, so external polls never eat a window's deltas out
        from under the history records."""
        total = int(jax.device_get(self._overflow))
        counters = self.engine.cache_counters(self.backend_state)
        prev = self._metrics_prev
        m: Dict[str, float] = {
            "overflow_dropped": total - int(prev.get("overflow", 0)),
            "overflow_dropped_total": total,
        }
        if counters:
            delta = {k: v - prev.get(k, 0.0) for k, v in counters.items()}
            m.update(self.engine.derive_cache_stats(delta))
            for k, v in self.engine.derive_cache_stats(counters).items():
                m[f"{k}_total"] = v
        if advance:
            self._metrics_prev = {"overflow": total, **counters}
        return m

    def suggest_capacity(self, history=None, safety: float = 1.25) -> int:
        """Recommend a dedup capacity from observed overflow (the first step
        of overflow-aware capacity autoscaling).

        Reads the PER-INTERVAL ``overflow_dropped`` records from ``history``
        (default: this trainer's own ``fit`` history, whose first interval
        starts at the step the counters were last zeroed — construction or
        resume): with no drops the current capacity stands; otherwise grow
        to the next power of two covering the current capacity plus
        ``safety`` x the worst observed per-step drop rate (powers of two
        keep routed shard divisibility).
        """
        hist = self.history if history is None else history
        worst = 0.0
        prev_step = self._metrics_base_step if history is None else 0
        for rec in hist:
            if "overflow_dropped" not in rec:
                continue
            d_steps = rec["step"] - prev_step
            if d_steps > 0:
                worst = max(worst, rec["overflow_dropped"] / d_steps)
            prev_step = rec["step"]
        if not hist and self.step_num > 0:
            # no logged records yet: fall back to the cumulative average
            # (the overflow counter spans the whole run — it is checkpointed)
            worst = self.overflow_dropped / self.step_num
        if worst <= 0:
            return self.engine.capacity
        return next_pow2(self.engine.capacity + safety * worst)

    def fit(self, batches: Iterator, steps: int, eval_fn=None) -> list:
        return _fit_loop(self, batches, steps, eval_fn)

    # ----------------------------------------------------- fault tolerance
    def _ckpt_tree(self):
        tree = {"dense": self.dense, "tables": self.tables,
                "accum": self.sparse_state.accum, "m": self.opt_state.m,
                "v_local": self.opt_state.v_local, "v_hat": self.opt_state.v_hat}
        if self.opt_state.ef is not None:
            tree["ef"] = self.opt_state.ef
        if jax.tree.leaves(self.backend_state):
            # cache-tier (or other stateful-placement) state is training
            # state: host tables alone are stale while rows sit dirty in the
            # device cache, so the cache must roundtrip with them.
            tree["bstate"] = self.backend_state
        # the overflow counter rides along so post-resume *_total metrics
        # share one baseline with the cache counters living in bstate
        tree["overflow"] = self._overflow
        return tree

    def _backend_sig(self):
        """Identity of the sparse physical layout baked into the tables
        (+ cache geometry, which shapes the checkpointed backend state)."""
        b = self.engine.backend
        sig = {"backend": type(b).__name__,
               "n_shards": getattr(b, "n_shards", 1),
               "store": self.engine.store.kind,
               "row_packing": {n: row_packing(t)
                               for n, t in self.tables.items()}}
        cache_rows = getattr(b, "cache_rows", None)
        if cache_rows is not None:
            sig["cache_rows"] = int(cache_rows)
        if self.engine.store.kind == "disk":
            # page geometry shapes the checkpoint's page files
            sig["page_rows"] = int(self.engine.store.page_rows)
        return sig

    def save(self):
        if self._prefetcher is not None and self._prefetcher.pending is not None:
            # flush-on-checkpoint: a checkpoint must capture the committed
            # (post-push) state — the speculative pull's cache admissions
            # would double-count on resume.  fit/train_step save at commit
            # boundaries before the next pull is dispatched.
            raise RuntimeError(
                "HybridTrainer.save: a prefetched pull is in flight — "
                "checkpoints capture committed state only; save at step "
                "boundaries (as fit/train_step do) before prefetching"
            )
        extras_dir = None
        if self.engine.store.kind == "disk":
            # commit everything in flight to the store, then snapshot its
            # pages SYNCHRONOUSLY into a staging dir — the async writer only
            # renames the finished snapshot into the checkpoint, so live
            # page mutations after this point can't tear it.  The staged
            # buffers/spill state in the npz tree stay consistent with the
            # snapshot: re-absorbing them on resume rewrites the same values
            # (absolute-row writes are idempotent).
            self.engine.sync_store(
                self.tables, self.sparse_state.accum, self.backend_state)
            extras_dir = os.path.join(
                self.ckpt.directory, f"pages_staging_{self.step_num}")
            if os.path.exists(extras_dir):
                shutil.rmtree(extras_dir)
            self.engine.store.snapshot_to(extras_dir)
        # checkpointing deliberately materializes device state host-side —
        # an allow-listed section under strict-transfers runs
        with jax.transfer_guard("allow"):
            self.ckpt.save(
                self.step_num, self._ckpt_tree(),
                meta={"n_pod": self.n_pod, "k": self.cfg.kstep.k,
                      **self._backend_sig()},
                extras_dir=extras_dir,
            )

    def resume(self) -> bool:
        if not self.ckpt:
            return False
        # Tables are checkpointed in the backend's physical layout; loading
        # them under a different backend (or routed shard count, which
        # changes the hash-slot permutation; or a cached run's host tables,
        # which are stale wherever rows sat dirty in the device cache)
        # would silently read wrong rows.
        s = latest_step(self.ckpt.directory)
        man = read_manifest(self.ckpt.directory, s) if s is not None else None
        if man is not None and "backend" in man.get("meta", {}):
            sig = self._backend_sig()
            saved = {k: man["meta"][k]
                     for k in ("backend", "n_shards", "cache_rows",
                               "store", "page_rows", "row_packing")
                     if k in man["meta"]}
            # checkpoints older than packing hold row-layout tables
            saved.setdefault("row_packing",
                             {n: 1 for n in sig["row_packing"]})
            # pre-store checkpoints carry no "store" key — they were host
            # runs, so only a disk-configured engine must refuse them
            if saved != {k: sig.get(k) for k in saved} or (
                "cache_rows" in sig and "cache_rows" not in saved
            ) or (sig["store"] == "disk" and "store" not in saved):
                raise ValueError(
                    f"checkpoint written with {saved} but the current engine "
                    f"uses {sig}: the tables' physical "
                    f"layouts differ — resume with the saving placement, or "
                    f"export/re-prepare the tables explicitly"
                )
        like = _drop_ef_if_absent(self._ckpt_tree(), self.ckpt)
        if man is not None and not any(
            k.split("/")[0] == "overflow" for k in man["leaves"]
        ):
            like.pop("overflow", None)   # pre-PR3 checkpoint: counter at 0
        step, tree = self.ckpt.restore_latest(like)
        if step is None:
            return False
        if self.engine.store.kind == "disk":
            # pages first: the restored npz state (staged buffers, cache
            # spill ids) is only consistent against the SAVE-TIME pages
            self.engine.store.restore_from(os.path.join(
                self.ckpt.directory, f"step_{step:010d}", "pages"))
            self.engine.reset_staging()
        self.step_num = step
        self.dense, self.tables = tree["dense"], tree["tables"]
        self.sparse_state = self.sparse_state._replace(accum=tree["accum"])
        self.backend_state = tree.get("bstate", self.backend_state)
        self.opt_state = self.opt_state._replace(
            step=jnp.asarray(step, jnp.int32), m=tree["m"],
            v_local=tree["v_local"], v_hat=tree["v_hat"],
            ef=tree.get("ef", self.opt_state.ef),
        )
        # restore the cumulative overflow counter and re-baseline the
        # interval snapshot so the first post-resume window reports only
        # post-resume deltas (totals keep the whole-run baseline, matching
        # the cache counters restored inside bstate)
        self._overflow = jnp.asarray(tree.get("overflow", 0), jnp.int32)
        self._commit_to_mesh()   # restored leaves are uncommitted host reads
        self._metrics_prev = {
            "overflow": int(jax.device_get(self._overflow)),
            **self.engine.cache_counters(self.backend_state),
        }
        self._metrics_base_step = step
        return True
