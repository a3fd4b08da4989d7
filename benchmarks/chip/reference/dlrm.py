"""Plain reference of MLPerf DLRM (arXiv:1906.00091): a bottom MLP over 13
dense features, 26 one-hot embedding tables, pairwise dot interaction of
the 27 vectors (lower triangle) next to the bottom output, and a top MLP
to one logit.

Straight ``jax.numpy`` in float32; every product goes through ``num``
(the reference, or the lower-precision control).  It
imports nothing of the program.  ``init_dense``
draws the values the program's factory documents: ``kb, kt =
split(key(seed))``, He-normal layer ``i`` of each MLP from
``fold_in(k, i)``, zero biases.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _names(cfg):
    return [f"emb_{i:02d}" for i in range(len(cfg["rows"]))]


def tables(cfg):
    d = int(cfg["embed_dim"])
    return [(n, int(r), d) for n, r in zip(_names(cfg), cfg["rows"])]


def _he(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / shape[0]) ** 0.5


def _mlp(key, sizes):
    return [{"w": _he(jax.random.fold_in(key, i), (sizes[i], sizes[i + 1])),
             "b": jnp.zeros((sizes[i + 1],), jnp.float32)}
            for i in range(len(sizes) - 1)]


def interact_dim(cfg) -> int:
    n = len(cfg["rows"]) + 1
    return n * (n - 1) // 2 + int(cfg["embed_dim"])


def init_dense(key, cfg):
    kb, kt = jax.random.split(key)
    return {"bot": _mlp(kb, [int(x) for x in cfg["bot_mlp"]]),
            "top": _mlp(kt, [interact_dim(cfg)]
                        + [int(x) for x in cfg["top_mlp"]])}


def table_ids(batch):
    ids = batch["sparse_ids"]
    return {f"emb_{i:02d}": ids[:, i:i + 1] for i in range(ids.shape[1])}


def embed(rows, local_ids, batch, cfg):
    """(B, 26, d): one row per table and instance."""
    return jnp.stack([rows[n][local_ids[n][:, 0]] for n in _names(cfg)],
                     axis=1)


def _mlp_apply(layers, x, num):
    for i, layer in enumerate(layers):
        x = num.einsum("bi,ij->bj", x, layer["w"]) + layer["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def forward(dense, emb, batch, cfg, num):
    """Logits (B,); every product goes through ``num``."""
    x = _mlp_apply(dense["bot"], batch["dense"], num)
    feats = jnp.concatenate([x[:, None, :], emb], axis=1)       # (B, 27, d)
    z = num.einsum("bfd,bgd->bfg", feats, feats)
    li, lj = jnp.tril_indices(feats.shape[1], k=-1)
    top_in = jnp.concatenate([x, z[:, li, lj]], axis=-1)
    return _mlp_apply(dense["top"], top_in, num)[:, 0]


def forward_flops(cfg) -> float:
    """Matmul FLOPs of one instance's forward pass (the interaction counts
    the lower triangle it keeps)."""
    d = int(cfg["embed_dim"])
    n = len(cfg["rows"]) + 1
    bot = [int(x) for x in cfg["bot_mlp"]]
    top = [interact_dim(cfg)] + [int(x) for x in cfg["top_mlp"]]
    mlp = sum(2 * a * b for s in (bot, top) for a, b in zip(s, s[1:]))
    return float(mlp + 2 * d * n * (n - 1) // 2)
