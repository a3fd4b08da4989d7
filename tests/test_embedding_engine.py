"""Embedding engine: bag lookup, working-set pull, sparse updates —
property tested (these are the paper's Algorithm 1 lines 3/11/13)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.embedding_engine import (
    EmbeddingEngine,
    TableSpec,
    embedding_bag,
    pull_working_set,
)
from repro.core.sparse_optim import SparseAdagrad, SparseAdagradConfig


@settings(max_examples=30, deadline=None)
@given(
    rows=st.integers(4, 200),
    dim=st.integers(1, 32),
    nnz=st.integers(1, 100),
    bags=st.integers(1, 40),
    combiner=st.sampled_from(["sum", "mean", "sqrtn"]),
    seed=st.integers(0, 999),
)
def test_bag_matches_dense_onehot(rows, dim, nnz, bags, combiner, seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, rows, nnz), jnp.int32)
    seg = jnp.asarray(rng.integers(0, bags, nnz), jnp.int32)
    w = jnp.asarray(rng.random(nnz), jnp.float32)
    out = embedding_bag(table, ids, seg, bags, weights=w, combiner=combiner)
    # dense one-hot oracle
    onehot = np.zeros((bags, nnz), np.float32)
    onehot[np.asarray(seg), np.arange(nnz)] = np.asarray(w)
    expect = onehot @ (np.asarray(table)[np.asarray(ids)])
    if combiner in ("mean", "sqrtn"):
        cnt = np.zeros(bags, np.float32)
        np.add.at(cnt, np.asarray(seg), 1.0)
        denom = np.maximum(cnt, 1.0)
        if combiner == "sqrtn":
            denom = np.sqrt(denom)
        expect = expect / denom[:, None]
    np.testing.assert_allclose(np.asarray(out), expect, atol=1e-4)


@settings(max_examples=30, deadline=None)
@given(
    rows=st.integers(4, 100),
    dim=st.integers(1, 16),
    nnz=st.integers(1, 80),
    bags=st.integers(1, 20),
    combiner=st.sampled_from(["sum", "mean", "sqrtn"]),
    seed=st.integers(0, 999),
)
def test_bag_from_working_matches_embedding_bag(rows, dim, nnz, bags,
                                                combiner, seed):
    """The working-set bag lookup must agree with ``embedding_bag`` for ALL
    supported combiners (the sqrtn branch used to silently fall through to
    sum)."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, rows, nnz), jnp.int32)
    seg = jnp.asarray(rng.integers(0, bags, nnz), jnp.int32)
    w = jnp.asarray(rng.random(nnz), jnp.float32)
    uids, inv = pull_working_set(ids, capacity=nnz)
    working = jnp.take(table, uids, axis=0)
    out_ws = EmbeddingEngine.bag_from_working(
        working, inv, seg, bags, weights=w, combiner=combiner
    )
    out_ref = embedding_bag(table, ids, seg, bags, weights=w,
                            combiner=combiner)
    np.testing.assert_allclose(np.asarray(out_ws), np.asarray(out_ref),
                               atol=1e-6)


def test_unknown_combiner_raises():
    """Unknown combiners are an error in BOTH lookup paths — never a silent
    fall-through to sum."""
    table = jnp.zeros((4, 2), jnp.float32)
    ids = jnp.zeros((3,), jnp.int32)
    seg = jnp.zeros((3,), jnp.int32)
    with pytest.raises(ValueError, match="combiner"):
        embedding_bag(table, ids, seg, 2, combiner="max")
    with pytest.raises(ValueError, match="combiner"):
        EmbeddingEngine.bag_from_working(table, ids, seg, 2, combiner="max")


@settings(max_examples=30, deadline=None)
@given(
    rows=st.integers(2, 500),
    nnz=st.integers(1, 200),
    seed=st.integers(0, 999),
)
def test_pull_working_set_roundtrip(rows, nnz, seed):
    """uids[inv] must reconstruct the original ids (the pull is lossless)."""
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, rows, nnz), jnp.int32)
    capacity = nnz  # worst case
    uids, inv = pull_working_set(ids, capacity)
    np.testing.assert_array_equal(np.asarray(uids)[np.asarray(inv)], np.asarray(ids))
    # dedup: real unique ids appear exactly once among the first n_unique
    n_unique = len(np.unique(np.asarray(ids)))
    assert len(np.unique(np.asarray(uids))) == n_unique


@settings(max_examples=25, deadline=None)
@given(
    rows=st.integers(4, 100),
    dim=st.integers(1, 16),
    nnz=st.integers(1, 64),
    seed=st.integers(0, 999),
)
def test_sparse_adagrad_equals_dense(rows, dim, nnz, seed):
    """Working-set AdaGrad must equal dense AdaGrad on the gathered grads."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    accum = jnp.asarray(rng.random((rows, dim)) + 0.1, jnp.float32)
    ids = jnp.asarray(rng.integers(0, rows, nnz), jnp.int32)
    uids, inv = pull_working_set(ids, nnz)
    # per-slot gradients, accumulated onto working rows like autodiff would
    slot_g = rng.standard_normal((nnz, dim)).astype(np.float32)
    row_g = np.zeros((nnz, dim), np.float32)
    np.add.at(row_g, np.asarray(inv), slot_g)
    sa = SparseAdagrad(SparseAdagradConfig(lr=0.1))
    nt, na = sa.apply_rows(table, accum, uids, jnp.asarray(row_g))
    dense_g = np.zeros((rows, dim), np.float32)
    np.add.at(dense_g, np.asarray(ids), slot_g)
    nt_ref, na_ref = sa.dense_reference(table, accum, jnp.asarray(dense_g))
    np.testing.assert_allclose(np.asarray(nt), np.asarray(nt_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(na), np.asarray(na_ref), atol=1e-5)


def test_engine_end_to_end():
    engine = EmbeddingEngine(
        {"t": TableSpec("t", rows=50, dim=4)}, capacity=16
    )
    tables = engine.init(jax.random.key(0))
    accum = engine.init_state(tables).accum
    states = engine.init_backend_state(tables)
    ids = jnp.asarray([3, 3, 7, 9, 3], jnp.int32)
    seg = jnp.asarray([0, 0, 1, 1, 2], jnp.int32)
    wss, _, _, _ = engine.pull(tables, accum, states, {"t": ids})
    ws = wss["t"]
    assert int(ws.n_dropped) == 0
    bags = engine.bag_from_working(ws.rows, ws.inverse, seg, num_bags=3)
    expect = embedding_bag(tables["t"], ids, seg, 3)
    np.testing.assert_allclose(np.asarray(bags), np.asarray(expect), atol=1e-6)
    assert engine.memory_bytes() == 50 * 4 * 4
    assert engine.cache_stats(states) == {}   # stateless placement


def test_engine_ids_from_batch_and_push():
    """Facade roundtrip: pull_batch -> push applies working-set AdaGrad."""
    engine = EmbeddingEngine(
        {"t": TableSpec("t", rows=40, dim=4, id_field="my_ids")}, capacity=8,
        optimizer=SparseAdagradConfig(lr=0.1),
    )
    tables = engine.init(jax.random.key(1))
    state = engine.init_state(tables)
    states = engine.init_backend_state(tables)
    batch = {"my_ids": jnp.asarray([[1, 2], [2, 5]], jnp.int32)}
    wss, tables_p, accum_p, states_p = engine.pull_batch(
        tables, state.accum, states, batch
    )
    # per-slot unit grads accumulated onto working rows, like autodiff would
    grads = {"t": jnp.zeros_like(wss["t"].rows).at[wss["t"].inverse].add(1.0)}
    new_tables, new_accum, _ = engine.push(
        tables_p, accum_p, states_p, wss, grads
    )
    # only the 3 touched rows moved
    moved = np.flatnonzero(
        np.any(np.asarray(new_tables["t"]) != np.asarray(tables["t"]), axis=1)
    )
    np.testing.assert_array_equal(moved, [1, 2, 5])
    assert int(engine.overflow(wss)) == 0


def test_gradient_through_pull_equals_direct():
    """d loss/d table via (pull -> working -> scatter) == direct path."""
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((30, 4)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 30, 20), jnp.int32)
    seg = jnp.asarray(np.sort(rng.integers(0, 5, 20)), jnp.int32)
    tgt = jnp.asarray(rng.standard_normal((5, 4)), jnp.float32)

    def loss_direct(t):
        return jnp.sum((embedding_bag(t, ids, seg, 5) - tgt) ** 2)

    uids, inv = pull_working_set(ids, 20)

    def loss_ws(working):
        emb = jnp.take(working, inv, axis=0)
        bags = jax.ops.segment_sum(emb, seg, num_segments=5)
        return jnp.sum((bags - tgt) ** 2)

    gt = jax.grad(loss_direct)(table)
    gw = jax.grad(loss_ws)(table[uids])
    gt2 = jnp.zeros_like(table).at[uids].add(gw)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(gt2), atol=1e-5)
