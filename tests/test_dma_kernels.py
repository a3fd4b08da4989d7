"""Interpret-mode checks of the DMA paths of the Mosaic kernels that the
small shapes of ``test_kernels.py`` / ``test_hash_map.py`` never reach:
hash probe chains that leave their home row of 128 buckets or wrap the
table, pushes spanning several row tiles, and the MXU bag formulation
(the one a TPU runs) against the jnp oracle, under ``vmap`` too."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.hash_map import (
    EMPTY,
    LANES,
    hash_bucket,
    hash_insert,
    hash_lookup_pallas,
)
from repro.kernels.sparse_adagrad import (
    adagrad_row_updates,
    gather_rows_pallas,
    sparse_adagrad_apply_pallas,
)


def _colliding(H, bucket, n):
    """``n`` ids whose home bucket is ``bucket`` in an H-bucket map."""
    cand = np.arange(0, 4_000_000, dtype=np.int32)
    home = np.asarray(hash_bucket(jnp.asarray(cand), H))
    ids = cand[home == bucket][:n]
    assert len(ids) == n
    return ids


@pytest.mark.parametrize("bucket", [LANES - 2, 1023])
def test_hash_probe_chain_crosses_rows(bucket):
    """A cluster starting two buckets before a 128-bucket row boundary
    (and one at the table's last bucket, which wraps to bucket 0) resolves
    every key exactly: far rows are fetched on demand."""
    H, C = 1024, 64
    ids = _colliding(H, bucket, 6)
    key_tab = jnp.full((H,), EMPTY, jnp.int32)
    slot_tab = jnp.zeros((H,), jnp.int32)
    slots = jnp.arange(len(ids), dtype=jnp.int32)
    key_tab, slot_tab, _ = hash_insert(
        key_tab, slot_tab, jnp.zeros((), jnp.int32), jnp.asarray(ids),
        slots, jnp.ones(len(ids), bool))
    slot_uid = jnp.full((C,), -1, jnp.int32).at[slots].set(
        jnp.asarray(ids))
    # a stale entry: its slot now holds another id -> miss
    slot_uid = slot_uid.at[3].set(-1)
    probe = jnp.asarray(np.concatenate([ids, [7, 99]]), jnp.int32)
    got = hash_lookup_pallas(key_tab, slot_tab, slot_uid, probe,
                             interpret=True)
    want = ref.hash_lookup_ref(key_tab, slot_tab, slot_uid, probe)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got)[3] == -1 and np.asarray(got)[5] == 5


@pytest.mark.parametrize("cap,dim", [(300, 64), (260, 130)])
def test_push_spans_row_tiles(cap, dim):
    """A push of several 128-row tiles, with pads that repeat entry 0 in the
    last tile, equals the jnp scatter-add bit for bit; the gather of the
    same rows returns them exactly."""
    rng = np.random.default_rng(cap)
    R = 2000
    table = jnp.asarray(rng.standard_normal((R, dim)), jnp.float32)
    accum = jnp.asarray(rng.random((R, dim)) + 0.1, jnp.float32)
    real = np.sort(rng.choice(R, size=cap - 9, replace=False))
    uids = jnp.asarray(np.concatenate([real, np.full(9, real[0])]),
                       jnp.int32)
    grads = jnp.asarray(rng.standard_normal((cap, dim)), jnp.float32)
    grads = grads.at[cap - 9:].set(0.0)
    delta, g2 = adagrad_row_updates(accum[uids], grads, table.dtype,
                                    lr=0.05, eps=1e-10)
    want_t, want_a = ref.sparse_adagrad_apply_ref(table, accum, uids,
                                                  delta, g2)
    got_t, got_a = sparse_adagrad_apply_pallas(table, accum, uids, delta, g2,
                                               interpret=True)
    assert np.array_equal(np.asarray(got_t), np.asarray(want_t))
    assert np.array_equal(np.asarray(got_a), np.asarray(want_a))
    rows = gather_rows_pallas(table, uids, interpret=True)
    assert np.array_equal(np.asarray(rows), np.asarray(table)[np.asarray(uids)])


@pytest.mark.parametrize("C,D,nnz,bags,bag_block,chunk", [
    (64, 32, 256, 128, 32, 128),
    (33, 17, 77, 13, 8, 32),
    (300, 64, 3000, 400, 256, 512),
])
def test_mxu_bag_matches_reference(C, D, nnz, bags, bag_block, chunk):
    """The MXU formulation (seg-sorted chunks, DMA'd rows, one-hot dot)
    equals the segment-sum oracle up to f32 reassociation."""
    rng = np.random.default_rng(C)
    working = jnp.asarray(rng.standard_normal((C, D)), jnp.float32)
    inv = jnp.asarray(rng.integers(0, C, nnz), jnp.int32)
    seg = jnp.asarray(rng.integers(0, bags, nnz), jnp.int32)
    w = jnp.asarray(rng.random(nnz), jnp.float32)
    got = embedding_bag_pallas(working, inv, seg, w, bags,
                               bag_block=bag_block, nnz_block=chunk,
                               interpret=True, exact=False)
    want = ref.embedding_bag_ref(working, inv, seg, w, bags)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batched_working", [False, True])
def test_mxu_bag_under_vmap(batched_working):
    """The batching rule folds the vmapped (pod) axis into one kernel call;
    the result equals the vmapped oracle, with the working set shared by
    the pods or batched with them."""
    rng = np.random.default_rng(5)
    P, C, D, nnz, bags = 3, 50, 8, 200, 30
    wshape = (P, C, D) if batched_working else (C, D)
    working = jnp.asarray(rng.standard_normal(wshape), jnp.float32)
    inv = jnp.asarray(rng.integers(0, C, (P, nnz)), jnp.int32)
    seg = jnp.asarray(rng.integers(0, bags, (P, nnz)), jnp.int32)
    w = jnp.asarray(rng.random((P, nnz)), jnp.float32)
    axes = (0 if batched_working else None, 0, 0, 0)
    got = jax.vmap(lambda *a: embedding_bag_pallas(
        *a, bags, interpret=True, exact=False), in_axes=axes)(
        working, inv, seg, w)
    want = jax.vmap(lambda *a: ref.embedding_bag_ref(*a, bags),
                    in_axes=axes)(working, inv, seg, w)
    assert got.shape == (P, bags, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
