"""Integration: end-to-end training behaviour — the paper's experimental
claims at CPU scale (k-step matches baseline AUC; crash/resume; online
predict-then-train)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.embedding_engine import EmbeddingEngine, TableSpec
from repro.core.kstep import KStepConfig
from repro.core.sparse_optim import SparseAdagrad, SparseAdagradConfig
from repro.data import synthetic as S
from repro.models import recsys as R
from repro.models import transformer as T
from repro.runtime.metrics import auc
from repro.runtime.trainer import DenseTrainer, HybridTrainer, TrainerConfig

# attn_heads=2: with 4 heads this tower fails to train on the synthetic
# stream at lr 1e-3 (AUC ~0.5 regardless of steps) — a calibration issue of
# the smoke setup, not of the k-step/sparse machinery under test.
CTR_CFG = R.CTRConfig(rows=5000, n_fields=8, nnz_per_instance=20, mlp=(64, 1),
                      attn_heads=2)


def ctr_trainer(n_pod, k, merge="flat", ckpt_dir=None, seed=0, backend=None):
    rng = jax.random.key(seed)
    dense = R.ctr_init_dense(rng, CTR_CFG)
    tc = TrainerConfig(
        n_pod=n_pod,
        kstep=KStepConfig(lr=1e-3, k=k, b1=0.0, merge=merge),
        sparse=SparseAdagradConfig(lr=0.5, initial_accumulator=0.01),
        ckpt_dir=ckpt_dir, ckpt_every=10, ckpt_async=False,
    )
    engine = EmbeddingEngine(
        {"sparse": TableSpec("sparse", rows=CTR_CFG.rows, dim=CTR_CFG.embed_dim,
                             id_field="ids")},
        capacity=8192, optimizer=SparseAdagrad(tc.sparse), backend=backend,
    )
    tables = engine.prepare(
        {"sparse": jax.random.normal(rng, (CTR_CFG.rows, CTR_CFG.embed_dim)) * 0.05}
    )
    return HybridTrainer(
        dense, engine, R.ctr_embed_from_workings(CTR_CFG),
        R.ctr_hybrid_loss(CTR_CFG), tc, tables=tables,
    )


def run_online(tr, steps, seed=1):
    """Paper §5 protocol: predict each batch with the current model, then
    train on it; report AUC over the last third."""
    gen = S.ctr_batches(seed=seed, batch=512, rows=CTR_CFG.rows,
                        n_fields=CTR_CFG.n_fields, nnz=CTR_CFG.nnz_per_instance)
    labels, scores = [], []
    for i in range(steps):
        b = next(gen)
        if i >= steps * 2 // 3:
            scores.append(tr.predict(b))
            labels.append(b["label"])
        tr.train_step(b)
    return auc(np.concatenate(labels), np.concatenate(scores))


def test_ctr_baseline_learns():
    a = run_online(ctr_trainer(n_pod=1, k=1), steps=120)
    assert a > 0.70, f"baseline AUC {a}"


def test_kstep_matches_baseline_auc():
    """Fig. 9: k-step merging must not hurt AUC measurably."""
    a_base = run_online(ctr_trainer(n_pod=1, k=1), steps=120)
    a_k = run_online(ctr_trainer(n_pod=4, k=10), steps=120)
    assert abs(a_base - a_k) < 0.03, (a_base, a_k)


def test_two_phase_and_int8_merges_learn():
    for merge in ("two_phase", "int8_ef"):
        a = run_online(ctr_trainer(n_pod=2, k=5, merge=merge), steps=100)
        assert a > 0.68, (merge, a)


def test_crash_resume_bitexact(tmp_path):
    """Fault tolerance: train 20, crash, resume from ckpt -> identical state
    to an uninterrupted run consuming the same stream."""
    d = str(tmp_path)
    t_ref = ctr_trainer(n_pod=2, k=5, seed=3)
    gen = S.ctr_batches(seed=9, batch=256, rows=CTR_CFG.rows,
                        n_fields=CTR_CFG.n_fields, nnz=CTR_CFG.nnz_per_instance)
    batches = [next(gen) for _ in range(30)]
    for b in batches:
        t_ref.train_step(b)

    t_a = ctr_trainer(n_pod=2, k=5, ckpt_dir=d, seed=3)
    for b in batches[:20]:
        t_a.train_step(b)
    del t_a  # crash after step 20 (ckpt_every=10 -> ckpt at 20 exists)

    t_b = ctr_trainer(n_pod=2, k=5, ckpt_dir=d, seed=3)
    assert t_b.resume()
    assert t_b.step_num == 20
    for b in batches[20:]:
        t_b.train_step(b)
    for a, b_ in zip(jax.tree.leaves(t_ref.tables), jax.tree.leaves(t_b.tables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)
    for a, b_ in zip(jax.tree.leaves(t_ref.dense), jax.tree.leaves(t_b.dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)


def test_int8_ef_residual_survives_resume(tmp_path):
    """merge="int8_ef": the error-feedback residual is optimizer state and
    must roundtrip through save/resume (dropping it re-zeros compensation)."""
    d = str(tmp_path)
    t_a = ctr_trainer(n_pod=2, k=5, merge="int8_ef", ckpt_dir=d, seed=3)
    gen = S.ctr_batches(seed=9, batch=256, rows=CTR_CFG.rows,
                        n_fields=CTR_CFG.n_fields, nnz=CTR_CFG.nnz_per_instance)
    for _ in range(10):   # merges at 5 and 10 -> nonzero residual; ckpt at 10
        t_a.train_step(next(gen))
    ef_ref = [np.asarray(x) for x in jax.tree.leaves(t_a.opt_state.ef)]
    assert max(float(np.abs(x).max()) for x in ef_ref) > 0.0

    t_b = ctr_trainer(n_pod=2, k=5, merge="int8_ef", ckpt_dir=d, seed=3)
    assert t_b.resume() and t_b.step_num == 10
    for a, b in zip(ef_ref, jax.tree.leaves(t_b.opt_state.ef)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_resume_int8_ef_from_pre_ef_checkpoint(tmp_path):
    """A checkpoint without the residual (older run / lossless merge) must
    resume cleanly under merge="int8_ef", keeping the zero residual."""
    d = str(tmp_path)
    t_a = ctr_trainer(n_pod=2, k=5, merge="flat", ckpt_dir=d, seed=3)
    gen = S.ctr_batches(seed=9, batch=256, rows=CTR_CFG.rows,
                        n_fields=CTR_CFG.n_fields, nnz=CTR_CFG.nnz_per_instance)
    for _ in range(10):
        t_a.train_step(next(gen))
    t_b = ctr_trainer(n_pod=2, k=5, merge="int8_ef", ckpt_dir=d, seed=3)
    assert t_b.resume() and t_b.step_num == 10
    for leaf in jax.tree.leaves(t_b.opt_state.ef):
        assert float(jnp.abs(leaf).max()) == 0.0


def test_resume_rejects_backend_mismatch(tmp_path):
    """Tables are checkpointed in the backend's physical layout; resuming
    under a different backend must fail loudly, not read wrong rows."""
    from repro.core.embedding_backend import make_backend
    d = str(tmp_path)
    t_a = ctr_trainer(n_pod=1, k=1, ckpt_dir=d)
    gen = S.ctr_batches(seed=9, batch=256, rows=CTR_CFG.rows,
                        n_fields=CTR_CFG.n_fields, nnz=CTR_CFG.nnz_per_instance)
    for _ in range(10):
        t_a.train_step(next(gen))
    t_b = ctr_trainer(n_pod=1, k=1, ckpt_dir=d, backend=make_backend("routed"))
    with pytest.raises(ValueError, match="physical"):
        t_b.resume()


def test_resume_rejects_other_row_packing(tmp_path):
    """A fused gather run keeps its 64-wide table packed two rows to a
    128-lane row, and checkpoints it so; resuming that into row-layout
    tables, or a row-layout checkpoint into packed ones, fails on the
    layout signature instead of on a shape or with wrong rows."""
    from repro.core.embedding_backend import GatherBackend, PackedRows
    gen = S.ctr_batches(seed=9, batch=64, rows=CTR_CFG.rows,
                        n_fields=CTR_CFG.n_fields, nnz=CTR_CFG.nnz_per_instance)
    batch = next(gen)
    for saver, resumer in ((True, False), (False, True)):
        d = str(tmp_path / f"packed_{saver}")
        t_a = ctr_trainer(n_pod=1, k=1, ckpt_dir=d,
                          backend=GatherBackend(fused=saver))
        assert isinstance(t_a.tables["sparse"], PackedRows) == saver
        t_a.train_step(batch)
        t_a.save()
        t_b = ctr_trainer(n_pod=1, k=1, ckpt_dir=d,
                          backend=GatherBackend(fused=resumer))
        with pytest.raises(ValueError, match="physical"):
            t_b.resume()
        t_c = ctr_trainer(n_pod=1, k=1, ckpt_dir=d,
                          backend=GatherBackend(fused=saver))
        assert t_c.resume() and t_c.step_num == 1
        np.testing.assert_array_equal(
            np.asarray(t_c.engine.export(t_c.tables)["sparse"]),
            np.asarray(t_a.engine.export(t_a.tables)["sparse"]))


def test_suggest_capacity_from_overflow():
    """Overflow-aware capacity autoscaling (step 1): a trainer that drops
    pulls recommends a larger power-of-two capacity; a clean trainer keeps
    its current one."""
    clean = ctr_trainer(n_pod=1, k=1)
    gen = S.ctr_batches(seed=9, batch=256, rows=CTR_CFG.rows,
                        n_fields=CTR_CFG.n_fields, nnz=CTR_CFG.nnz_per_instance)
    clean.cfg.log_every = 2
    clean.fit(gen, 4)
    assert clean.overflow_dropped == 0
    assert clean.suggest_capacity() == clean.engine.capacity

    # synthetic history (PER-INTERVAL records, as sparse_metrics emits):
    # 300 drops in the 2-step window -> 150/step
    # -> needs >= 8192 + 1.25 * 150 -> next pow2 = 16384
    hist = [{"step": 2, "overflow_dropped": 300}]
    assert clean.suggest_capacity(history=hist) == 16384

    # live overflow: capacity 64 cannot hold ~2k distinct ids per batch
    from repro.runtime.factory import build_trainer
    tight = build_trainer("baidu-ctr", TrainerConfig(
        n_pod=1, kstep=KStepConfig(lr=1e-3, k=1, b1=0.0),
        sparse=SparseAdagradConfig(lr=0.5, initial_accumulator=0.01),
        capacity=64, log_every=2,
    ))
    gen = S.ctr_batches(seed=1, batch=256, rows=20000, n_fields=8, nnz=20)
    tight.fit(gen, 4)
    assert tight.overflow_dropped > 0
    suggested = tight.suggest_capacity()
    assert suggested > 64 and (suggested & (suggested - 1)) == 0


def test_dense_trainer_lm_learns_and_resumes(tmp_path):
    cfg = T.TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                              d_ff=128, vocab=64, dtype=jnp.float32, moe_group_size=64)
    p = T.init_params(jax.random.key(1), cfg)
    tc = TrainerConfig(n_pod=2, kstep=KStepConfig(lr=2e-3, k=5, b1=0.9),
                       ckpt_dir=str(tmp_path), ckpt_every=20, ckpt_async=False)
    tr = DenseTrainer(lambda pp, bb: T.loss_fn(pp, bb, cfg), p, tc)
    gen = S.lm_batches(seed=0, batch=16, seq_len=32, vocab=64)
    losses = [tr.train_step(next(gen)) for _ in range(40)]
    assert losses[-1] < losses[0] - 1.0
    tr2 = DenseTrainer(lambda pp, bb: T.loss_fn(pp, bb, cfg), p, tc)
    assert tr2.resume() and tr2.step_num == 40


def test_overflow_counter_survives_resume(tmp_path):
    """The cumulative overflow counter is training state: it rides the
    checkpoint so post-resume ``*_total`` metrics share one baseline with
    the cache counters (which live inside the checkpointed bstate)."""
    from repro.runtime.factory import build_trainer
    tcfg = TrainerConfig(
        n_pod=1, kstep=KStepConfig(lr=1e-3, k=1, b1=0.0),
        sparse=SparseAdagradConfig(lr=0.5, initial_accumulator=0.01),
        capacity=64, ckpt_dir=str(tmp_path), ckpt_every=4, ckpt_async=False,
    )
    tr = build_trainer("baidu-ctr", tcfg)
    gen = S.ctr_batches(seed=1, batch=256, rows=20000, n_fields=8, nnz=20)
    for _ in range(4):
        tr.train_step(next(gen))
    assert tr.overflow_dropped > 0
    tr2 = build_trainer("baidu-ctr", tcfg)
    assert tr2.resume() and tr2.step_num == 4
    assert tr2.overflow_dropped == tr.overflow_dropped
    # the first post-resume interval reports only post-resume drops
    m = tr2.sparse_metrics()
    assert m["overflow_dropped"] == 0
    assert m["overflow_dropped_total"] == tr.overflow_dropped


def _lm_cfg():
    return T.TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                               d_ff=128, vocab=64, dtype=jnp.float32,
                               moe_group_size=64)


def test_dense_merge_delay_converges():
    """merge_delay>0 (async DCN-hiding merges): the delayed application
    x <- merged + (x_now - x_snapshot) must still learn on the
    quickstart-scale smoke config and track the synchronous-merge loss."""
    cfg = _lm_cfg()
    p = T.init_params(jax.random.key(1), cfg)

    def run(delay):
        tc = TrainerConfig(n_pod=4, kstep=KStepConfig(lr=2e-3, k=10, b1=0.9),
                           merge_delay=delay)
        tr = DenseTrainer(lambda pp, bb: T.loss_fn(pp, bb, cfg), p, tc)
        gen = S.lm_batches(seed=0, batch=16, seq_len=32, vocab=64)
        return tr, [float(tr.train_step(next(gen))) for _ in range(60)]

    tr0, l0 = run(0)
    tr2, l2 = run(2)
    assert l2[-1] < l2[0] - 1.0, "delayed merges must still converge"
    assert abs(l2[-1] - l0[-1]) < 0.5, (l0[-1], l2[-1])
    # the pipeline reached steady state: exactly `delay` merges in flight
    assert len(tr2._pending_merges) == 2
    assert len(tr0._pending_merges) == 0


def test_dense_merge_delay_resumes(tmp_path):
    """The in-flight delayed-merge queue is not checkpointed; resume starts
    with an empty queue and keeps training."""
    cfg = _lm_cfg()
    p = T.init_params(jax.random.key(1), cfg)
    tc = TrainerConfig(n_pod=2, kstep=KStepConfig(lr=2e-3, k=5, b1=0.9),
                       merge_delay=1, ckpt_dir=str(tmp_path), ckpt_every=20,
                       ckpt_async=False)
    tr = DenseTrainer(lambda pp, bb: T.loss_fn(pp, bb, cfg), p, tc)
    gen = S.lm_batches(seed=0, batch=16, seq_len=32, vocab=64)
    for _ in range(20):
        tr.train_step(next(gen))
    tr2 = DenseTrainer(lambda pp, bb: T.loss_fn(pp, bb, cfg), p, tc)
    assert tr2.resume() and tr2.step_num == 20
    assert len(tr2._pending_merges) == 0
    assert np.isfinite(float(tr2.train_step(next(gen))))


def test_dead_knobs_rejected_loudly():
    """The no-silent-config contract: documented knobs a trainer cannot
    honor raise at construction instead of being ignored."""
    from repro.runtime.factory import build_trainer

    # HybridTrainer has no delayed dense merge (sparse syncs every step)
    with pytest.raises(ValueError, match="merge_delay"):
        build_trainer("baidu-ctr", TrainerConfig(n_pod=1, merge_delay=1))
    # merge_quorum < 1.0 has no failure detector behind it anywhere yet
    with pytest.raises(NotImplementedError, match="merge_quorum"):
        build_trainer("baidu-ctr", TrainerConfig(n_pod=1, merge_quorum=0.5))
    cfg = _lm_cfg()
    p = T.init_params(jax.random.key(1), cfg)
    with pytest.raises(NotImplementedError, match="merge_quorum"):
        DenseTrainer(lambda pp, bb: T.loss_fn(pp, bb, cfg), p,
                     TrainerConfig(n_pod=2, merge_quorum=0.75))
    # int8_ef's error feedback requires the fused merge path
    with pytest.raises(NotImplementedError, match="int8_ef"):
        DenseTrainer(
            lambda pp, bb: T.loss_fn(pp, bb, cfg), p,
            TrainerConfig(n_pod=2, merge_delay=1,
                          kstep=KStepConfig(merge="int8_ef")),
        )
    with pytest.raises(ValueError, match="merge_delay"):
        DenseTrainer(lambda pp, bb: T.loss_fn(pp, bb, cfg), p,
                     TrainerConfig(n_pod=2, merge_delay=-1))


def test_merge_quorum_subset_average():
    """Straggler mitigation: merging over a pod subset is a valid merge —
    params equal the subset mean, stragglers keep their local value."""
    from repro.core.kstep import KStepAdam, pod_replicate
    pp = pod_replicate({"x": jnp.zeros(4)}, 4)
    opt = KStepAdam(KStepConfig(lr=0.1, k=1), n_pod=4)
    state = opt.init(pp)
    g = jax.tree.map(
        lambda x: jnp.arange(4.0).reshape(4, 1) * jnp.ones_like(x), pp)
    p1, state = opt.step(pp, g, state, merge=False)
    # emulate quorum merge of pods {0,1,2}: average their replicas only
    subset = jax.tree.map(lambda x: x.at[:3].set(jnp.mean(x[:3], 0)), p1)
    for leaf in jax.tree.leaves(subset):
        assert np.allclose(leaf[0], leaf[1])
        assert not np.allclose(leaf[3], leaf[0])
