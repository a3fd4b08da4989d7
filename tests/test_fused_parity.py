"""Fused-kernel bit-parity suite (the acceptance contract of the fusion PR).

The fused Pallas hot path — gather+bag pull, scatter+AdaGrad push, and the
cache-tier double-indirection variants — must be BIT-identical to the
unfused jnp expressions on every backend, forward and gradient.  Anything
weaker would make ``--fused-kernels`` a numerics knob instead of a perf
knob, and fused-vs-unfused loss curves would silently diverge.

Property tests (hypothesis) sweep odd geometries, all combiners,
weighted/unweighted bags and drop-row traffic; the remaining tests check
the backend objects and a short end-to-end fit.  The suite runs under ``REPRO_KERNEL_INTERPRET=1`` (set by
conftest), so fused ops execute through Pallas interpret mode — the same
kernel code that compiles on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.cache_tier import CachedBackend
from repro.core.embedding_backend import GatherBackend, make_backend
from repro.core.embedding_engine import EmbeddingEngine
from repro.core.sparse_optim import SparseAdagrad, SparseAdagradConfig


def _bitwise(a, b, msg=""):
    __tracebackhint__ = True
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, msg
    assert np.array_equal(a, b, equal_nan=True), (
        f"{msg}: max |diff| = {np.abs(a.astype(np.float64) - b.astype(np.float64)).max()}"
    )


# ------------------------------------------------------------- bag property
@settings(max_examples=20, deadline=None)
@given(
    cap=st.integers(3, 40),
    dim=st.integers(1, 33),
    nnz=st.integers(1, 97),
    bags=st.integers(1, 19),
    combiner=st.sampled_from(["sum", "mean", "sqrtn"]),
    weighted=st.booleans(),
)
def test_bag_fused_matches_unfused(cap, dim, nnz, bags, combiner, weighted):
    """Forward AND gradient of the fused gather+bag are bit-identical to the
    unfused reference for arbitrary odd geometries, including id slots that
    point at the zero drop row (``inverse == cap``)."""
    rng = np.random.default_rng(cap * 1_000_003 + dim * 101 + nnz * 7 + bags)
    working = jnp.asarray(
        rng.standard_normal((cap + 1, dim)), jnp.float32
    ).at[cap].set(0.0)
    inv = jnp.asarray(rng.integers(0, cap + 1, nnz), jnp.int32)
    seg = jnp.asarray(np.sort(rng.integers(0, bags, nnz)), jnp.int32)
    w = jnp.asarray(rng.standard_normal(nnz), jnp.float32) if weighted else None

    def bag(wk, fused):
        return EmbeddingEngine.bag_from_working(
            wk, inv, seg, bags, w, combiner, fused=fused)

    out_u, vjp_u = jax.vjp(lambda wk: bag(wk, False), working)
    out_f, vjp_f = jax.vjp(lambda wk: bag(wk, True), working)
    _bitwise(out_f, out_u, f"bag fwd {combiner} weighted={weighted}")

    ct = jnp.asarray(rng.standard_normal((bags, dim)), jnp.float32)
    _bitwise(vjp_f(ct)[0], vjp_u(ct)[0],
             f"bag grad {combiner} weighted={weighted}")


def _pad_slots(uids) -> np.ndarray:
    """Boolean mask of working-set slots that are capacity pads (duplicates
    of an already-present id, the ``pull_working_set`` fill convention)."""
    u = np.asarray(uids)
    mask = np.ones(u.shape[0], bool)
    _, first = np.unique(u, return_index=True)
    mask[first] = False
    return mask


# ----------------------------------------------------- push property (drop)
@settings(max_examples=10, deadline=None)
@given(
    rows=st.integers(8, 64),
    dim=st.integers(1, 16),
    n_ids=st.integers(1, 80),
    cap=st.integers(4, 12),
)
def test_gather_push_fused_matches_unfused(rows, dim, n_ids, cap):
    """Fused scatter+AdaGrad push == unfused push, bit for bit — including
    batches that overflow ``cap`` (drop-row gradient discarded identically)
    and rows the batch never touched (bit-unchanged)."""
    rng = np.random.default_rng(rows * 7919 + dim * 31 + n_ids)
    opt = SparseAdagrad(SparseAdagradConfig(lr=0.1))
    table = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    accum = jnp.asarray(rng.random((rows, dim)) + 0.05, jnp.float32)
    ids = jnp.asarray(rng.integers(0, rows, n_ids), jnp.int32)
    # drop-row slot gets a nonzero gradient; both paths must discard it.
    # Pad slots (uids padded by REPEATING an existing id) must carry zero
    # gradient — that is the pipeline invariant (``inverse`` only references
    # the canonical slot, so the bag gradient never lands on a pad).
    row_g = jnp.asarray(rng.standard_normal((cap + 1, dim)) * 2, jnp.float32)
    row_g = row_g.at[:cap].set(jnp.where(
        _pad_slots(GatherBackend().pull(table, accum, (), ids, cap)[0].uids)[
            :, None],
        0.0, row_g[:cap]))

    outs = {}
    for fused in (False, True):
        be = GatherBackend(fused=fused)
        st_ = be.init_state(table)
        ws, t, a, st_ = be.pull(table, accum, st_, ids, cap)
        outs[fused] = be.push(t, a, st_, ws, row_g, opt)[:2]
    (tu, au), (tf, af) = outs[False], outs[True]
    _bitwise(tf, tu, "pushed table")
    _bitwise(af, au, "pushed accum")

    touched = np.zeros(rows, bool)
    touched[np.unique(np.asarray(ids))] = True
    _bitwise(np.asarray(tf)[~touched], np.asarray(table)[~touched],
             "untouched rows")


# -------------------------------------------------------- cached backend
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cached_fused_matches_unfused(seed):
    """Full-mirror CachedBackend: fused pull (double-indirection gather) and
    fused push (id->slot folded into the kernel index stream) are
    bit-identical to the unfused cache path across several steps, including
    the flushed-back table/accumulator."""
    rng = np.random.default_rng(seed)
    rows, dim, cap = 48, 6, 32
    opt = SparseAdagrad(SparseAdagradConfig(lr=0.1))
    table = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    accum = jnp.full((rows, dim), 0.1, jnp.float32)

    steps = [
        (jnp.asarray(rng.integers(0, rows, 40), jnp.int32),
         jnp.asarray(rng.standard_normal((cap + 1, dim)), jnp.float32))
        for _ in range(3)
    ]

    def run(fused):
        be = CachedBackend(cache_rows=rows, fused=fused)
        t, a = be.prepare(table), jnp.array(accum)
        st_ = be.init_state(t)
        pulled = []
        for ids, row_g in steps:
            ws, t, a, st_ = be.pull(t, a, st_, ids, cap)
            pulled.append(ws.rows)
            # pipeline invariant: capacity-pad slots carry zero gradient
            # (uids are identical on both sides, so the masking is too)
            row_g = row_g.at[:cap].set(jnp.where(
                _pad_slots(ws.uids)[:, None], 0.0, row_g[:cap]))
            t, a, st_ = be.push(t, a, st_, ws, row_g, opt)
        t, a, st_ = be.flush(t, a, st_)
        return pulled, be.export(t), be.export(a)

    pu, tu, au = run(False)
    pf, tf, af = run(True)
    for i, (ru, rf) in enumerate(zip(pu, pf)):
        _bitwise(rf, ru, f"cached pulled rows, step {i}")
    _bitwise(tf, tu, "flushed table")
    _bitwise(af, au, "flushed accum")


# -------------------------------------------------------------- end-to-end
@pytest.mark.parametrize("placement", ["gather", "cached", "routed"])
def test_fit_fused_matches_unfused(placement):
    """Six online steps through the real trainer: the per-step loss floats
    are identical with ``fused_kernels`` off and on.  (For routed, fusion
    covers the bag only — the push stays inside the reverse route — so this
    doubles as the no-op-safety check.)"""
    from repro import configs
    from repro.data import synthetic as S
    from repro.runtime.factory import build_trainer
    from repro.runtime.online import fit_online
    from repro.runtime.trainer import TrainerConfig

    def run(fused):
        cfg = configs.get("baidu-ctr").smoke_cfg
        tcfg = TrainerConfig(
            n_pod=2, placement=placement, capacity=256,
            cache_rows=256 if placement == "cached" else None,
            fused_kernels=fused, log_every=1,
        )
        tr = build_trainer("baidu-ctr", tcfg, seed=3)
        gen = S.recsys_batches(cfg, batch=32, seed=5)
        hist, _ = fit_online(tr, gen, 6, window=5)
        return [float(h["loss"]) for h in hist]

    unfused, fused = run(False), run(True)
    assert len(unfused) == 6
    assert unfused == fused, f"loss drift: {unfused} vs {fused}"
