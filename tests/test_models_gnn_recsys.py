"""GIN + recsys model correctness."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models import gin as G
from repro.models import recsys as R


# ------------------------------------------------------------------- GIN
@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(3, 40),
    e=st.integers(1, 150),
    seed=st.integers(0, 99),
)
def test_gin_matches_dense_adjacency(n, e, seed):
    rng = np.random.default_rng(seed)
    cfg = G.GINConfig(n_layers=3, d_in=6, d_hidden=8, n_classes=3)
    params = G.init_params(jax.random.key(seed), cfg)
    x = jnp.asarray(rng.standard_normal((n, 6)), jnp.float32)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    out = G.forward(params, x, src, dst, cfg)
    adj = jnp.zeros((n, n)).at[src, dst].add(1.0)
    ref = G.dense_reference_forward(params, x, adj, cfg)
    # f32 accumulation order differs (segment_sum vs matmul); relus amplify
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2)


def test_gin_edge_mask_removes_messages():
    cfg = G.GINConfig(n_layers=2, d_in=4, d_hidden=8, n_classes=2)
    params = G.init_params(jax.random.key(0), cfg)
    x = jnp.ones((6, 4))
    src = jnp.asarray([0, 1, 2], jnp.int32)
    dst = jnp.asarray([3, 4, 5], jnp.int32)
    full = G.forward(params, x, src, dst, cfg,
                     edge_mask=jnp.ones(3))
    masked = G.forward(params, x, src, dst, cfg,
                       edge_mask=jnp.asarray([1.0, 0.0, 1.0]))
    none_ = G.forward(params, x, src[:2], dst[:2], cfg,
                      edge_mask=jnp.asarray([1.0, 0.0]))
    assert not np.allclose(np.asarray(full), np.asarray(masked))
    np.testing.assert_allclose(np.asarray(masked[5]), np.asarray(full[5]), atol=1e-6)


def test_gin_graph_readout():
    cfg = G.GINConfig(n_layers=2, d_in=4, d_hidden=8, n_classes=3, readout="graph")
    params = G.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.standard_normal((20, 4)), jnp.float32),
        "edge_src": jnp.asarray(rng.integers(0, 20, 30), jnp.int32),
        "edge_dst": jnp.asarray(rng.integers(0, 20, 30), jnp.int32),
        "graph_ids": jnp.asarray(np.repeat(np.arange(4), 5), jnp.int32),
        "labels": jnp.asarray([0, 1, 2, 0], jnp.int32),
    }
    loss = G.loss_fn(params, batch, cfg)
    g = jax.grad(G.loss_fn)(params, batch, cfg)
    assert np.isfinite(float(loss))
    for leaf in jax.tree.leaves(g):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_gin_node_mask_loss():
    cfg = G.GINConfig(n_layers=2, d_in=4, d_hidden=8, n_classes=3)
    params = G.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.standard_normal((10, 4)), jnp.float32),
        "edge_src": jnp.asarray(rng.integers(0, 10, 20), jnp.int32),
        "edge_dst": jnp.asarray(rng.integers(0, 10, 20), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 3, 10), jnp.int32),
        "node_mask": jnp.asarray([1.0] * 3 + [0.0] * 7),
    }
    l1 = G.loss_fn(params, batch, cfg)
    batch2 = dict(batch, labels=batch["labels"].at[5].set(
        (batch["labels"][5] + 1) % 3))
    l2 = G.loss_fn(params, batch2, cfg)
    assert abs(float(l1) - float(l2)) < 1e-9  # masked node label irrelevant


# ---------------------------------------------------------------- recsys
def test_dlrm_interaction_count():
    cfg = R.DLRMConfig(rows=tuple([10] * 26))
    assert cfg.interact_dim == 27 * 26 // 2 + 128
    feats = jnp.asarray(np.random.default_rng(0).standard_normal((4, 5, 3)), jnp.float32)
    inter = R.dot_interaction(feats)
    assert inter.shape == (4, 10)
    z = np.einsum("bfd,bgd->bfg", np.asarray(feats), np.asarray(feats))
    li, lj = np.tril_indices(5, -1)
    np.testing.assert_allclose(np.asarray(inter), z[:, li, lj], atol=1e-5)


def test_din_attention_mask():
    """Masked history positions must not influence the output."""
    cfg = R.DINConfig(item_vocab=100, seq_len=8)
    dense = R.din_init_dense(jax.random.key(0), cfg)
    tables = {"items": jax.random.normal(jax.random.key(1), (100, 18)) * 0.1}
    rng = np.random.default_rng(0)
    hist = rng.integers(0, 100, (2, 8))
    batch1 = {
        "hist_ids": jnp.asarray(hist, jnp.int32),
        "hist_mask": jnp.asarray([[1, 1, 1, 0, 0, 0, 0, 0]] * 2, jnp.float32),
        "target_id": jnp.asarray([5, 7], jnp.int32),
    }
    hist2 = hist.copy()
    hist2[:, 5] = (hist2[:, 5] + 13) % 100  # change a masked position
    batch2 = dict(batch1, hist_ids=jnp.asarray(hist2, jnp.int32))
    e1 = R.din_embed_batch(tables, batch1, cfg)
    e2 = R.din_embed_batch(tables, batch2, cfg)
    o1 = R.din_forward_from_emb(dense, e1, batch1, cfg)
    o2 = R.din_forward_from_emb(dense, e2, batch2, cfg)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_dien_augru_attention_effect():
    """AUGRU (DIEN eq. 5): zero attention freezes the hidden state; full
    attention recovers the plain GRU."""
    cfg = R.DINConfig(name="dien", item_vocab=50, seq_len=6, gru_dim=12)
    dense = R.din_init_dense(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((6, 3, 12)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((3, 12)), jnp.float32)
    zeros_att = jnp.zeros((6, 3))
    _, final = R._gru_scan(dense["augru"], xs, h0, att=zeros_att)
    np.testing.assert_allclose(np.asarray(final), np.asarray(h0), atol=1e-6)
    ones_att = jnp.ones((6, 3))
    _, final_plain = R._gru_scan(dense["augru"], xs, h0)
    _, final_ones = R._gru_scan(dense["augru"], xs, h0, att=ones_att)
    np.testing.assert_allclose(np.asarray(final_ones), np.asarray(final_plain), atol=1e-6)


def test_two_tower_inbatch_softmax_and_logq():
    cfg = R.TwoTowerConfig(item_vocab=100, embed_dim=8, tower_mlp=(16, 8), user_hist_len=4)
    dense = R.two_tower_init_dense(jax.random.key(0), cfg)
    tables = {"items": jax.random.normal(jax.random.key(1), (100, 8)) * 0.1}
    rng = np.random.default_rng(0)
    batch = {
        "user_ids": jnp.asarray(rng.integers(0, 100, (4, 4)), jnp.int32),
        "user_mask": jnp.ones((4, 4)),
        "item_id": jnp.asarray(rng.integers(0, 100, 4), jnp.int32),
    }
    emb = R.two_tower_embed_batch(tables, batch, cfg)
    l1 = R.two_tower_loss(dense, emb, batch, cfg)
    l2 = R.two_tower_loss(dense, emb, {**batch, "sample_logq": jnp.ones(4)}, cfg)
    assert np.isfinite(float(l1)) and np.isfinite(float(l2))
    # positive logQ on negatives downweights them -> loss strictly decreases
    assert float(l2) < float(l1)
    # capped-pool path must equal the full in-batch softmax when pool >= B
    u, v = R.two_tower_forward_from_emb(dense, emb, batch, cfg)
    logits = np.asarray(u @ v.T, np.float64) / cfg.temperature
    lse = np.log(np.exp(logits).sum(1))
    full = float(np.mean(lse - np.diag(logits)))
    np.testing.assert_allclose(float(l1), full, rtol=1e-4)


def test_two_tower_retrieval_scores():
    cfg = R.TwoTowerConfig(item_vocab=100, embed_dim=8, tower_mlp=(16, 8), user_hist_len=4)
    dense = R.two_tower_init_dense(jax.random.key(0), cfg)
    tables = {"items": jax.random.normal(jax.random.key(1), (100, 8)) * 0.1}
    user_emb = jax.random.normal(jax.random.key(2), (2, 8))
    scores = R.two_tower_score_candidates(dense, tables, user_emb, jnp.arange(50), cfg)
    assert scores.shape == (2, 50)
    # normalized towers: scores bounded by 1
    assert float(jnp.max(jnp.abs(scores))) <= 1.0 + 1e-5


def test_ctr_model_field_attention():
    cfg = R.CTRConfig(rows=100, n_fields=4, nnz_per_instance=6, mlp=(16, 1), attn_heads=2)
    dense = R.ctr_init_dense(jax.random.key(0), cfg)
    tables = {"sparse": jax.random.normal(jax.random.key(1), (100, 64)) * 0.1}
    rng = np.random.default_rng(0)
    batch = {
        "ids": jnp.asarray(rng.integers(0, 100, (3, 6)), jnp.int32),
        "field_ids": jnp.asarray(rng.integers(0, 4, (3, 6)), jnp.int32),
        "mask": jnp.ones((3, 6)),
    }
    emb = R.ctr_embed_batch(tables, batch, cfg)
    assert emb.shape == (3, 4, 64)
    out = R.ctr_forward_from_emb(dense, emb, batch, cfg)
    assert out.shape == (3,) and np.all(np.isfinite(np.asarray(out)))


# ------------------------------------------------- config-knob regressions
def test_gin_train_eps_gates_eps_gradient():
    """``train_eps`` (found dead by repro.analysis) now gates the GIN-0
    self-weight: the forward pass is identical either way, but gradients
    reach eps only when the knob is on."""
    rng = np.random.default_rng(1)
    batch = {
        "x": jnp.asarray(rng.standard_normal((10, 4)), jnp.float32),
        "edge_src": jnp.asarray(rng.integers(0, 10, 20), jnp.int32),
        "edge_dst": jnp.asarray(rng.integers(0, 10, 20), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 3, 10), jnp.int32),
    }
    frozen = G.GINConfig(n_layers=2, d_in=4, d_hidden=8, n_classes=3,
                         train_eps=False)
    learned = G.GINConfig(n_layers=2, d_in=4, d_hidden=8, n_classes=3,
                          train_eps=True)
    params = G.init_params(jax.random.key(0), frozen)
    np.testing.assert_array_equal(
        np.asarray(G.loss_fn(params, batch, frozen)),
        np.asarray(G.loss_fn(params, batch, learned)))
    g_frozen = jax.grad(G.loss_fn)(params, batch, frozen)
    g_learned = jax.grad(G.loss_fn)(params, batch, learned)
    assert np.all(np.asarray(g_frozen["eps"]) == 0.0)
    assert np.any(np.asarray(g_learned["eps"]) != 0.0)


def test_two_tower_spec_declares_mean_and_pools_by_it():
    """``TableSpec.combiner`` (found dead by repro.analysis) now drives the
    user-history pooling: the two-tower bag is a mean over the padded
    history window, not a raw sum."""
    cfg = R.TwoTowerConfig(item_vocab=20, embed_dim=4, tower_mlp=(4,),
                           user_hist_len=3)
    assert R.two_tower_table_specs(cfg)["items"].combiner == "mean"
    rng = np.random.default_rng(0)
    tables = {"items": jnp.asarray(rng.standard_normal((20, 4)), jnp.float32)}
    batch = {
        "user_ids": jnp.asarray(rng.integers(0, 20, (2, 3)), jnp.int32),
        "user_mask": jnp.asarray([[1, 1, 0], [1, 0, 0]], jnp.float32),
        "item_id": jnp.asarray([3, 7], jnp.int32),
    }
    emb = R.two_tower_embed_batch(tables, batch, cfg)
    rows = np.asarray(tables["items"])[np.asarray(batch["user_ids"])]
    manual = (np.asarray(batch["user_mask"])[..., None] * rows).sum(1) / 3
    np.testing.assert_allclose(np.asarray(emb["user"]), manual,
                               rtol=1e-6, atol=1e-6)


def test_ctr_workings_adapter_matches_direct_bag():
    """The working-set adapter pools with the same spec combiner as the
    direct path — bit-exact when the working set is the table itself."""
    rng = np.random.default_rng(2)
    cfg = R.CTRConfig(rows=64, embed_dim=8, n_fields=3, nnz_per_instance=5)
    table = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    batch = {
        "ids": jnp.asarray(rng.integers(0, 64, (4, 5)), jnp.int32),
        "field_ids": jnp.asarray(rng.integers(0, 3, (4, 5)), jnp.int32),
        "mask": jnp.asarray(rng.integers(0, 2, (4, 5)), jnp.float32),
    }
    direct = R.ctr_embed_batch({"sparse": table}, batch, cfg)
    via_ws = R.ctr_embed_from_workings(cfg)(
        {"sparse": table}, {"sparse": batch["ids"].reshape(-1)}, batch)
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(via_ws))
