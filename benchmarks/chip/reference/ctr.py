"""Plain reference of the paper's CTR model (Zhao et al. 2022, Fig. 2):
multi-hot ids -> per-field sum bags of 64-wide rows -> field
self-attention with a residual -> ReLU MLP -> one logit.

Straight ``jax.numpy`` with no kernels, no working set and no batching
tricks, in float32.  Every product goes through ``num``
(``harness.refstep.Numerics``), so the same code is the reference and the
lower-precision control.  It imports nothing of the
program.  ``init_dense`` draws the same initial values the
program's factory documents: He-normal matrices (std sqrt(2 / fan_in))
from ``split(key(seed), 6)``, MLP layer ``i`` from ``fold_in(k[3], i)``,
zero biases.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tables(cfg):
    """[(name, rows, dim)] in the program's table order (sorted names)."""
    return [("sparse", int(cfg["rows"]), int(cfg["embed_dim"]))]


def _he(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / shape[0]) ** 0.5


def _mlp(key, sizes):
    return [{"w": _he(jax.random.fold_in(key, i), (sizes[i], sizes[i + 1])),
             "b": jnp.zeros((sizes[i + 1],), jnp.float32)}
            for i in range(len(sizes) - 1)]


def init_dense(key, cfg):
    d, f = int(cfg["embed_dim"]), int(cfg["n_fields"])
    k = jax.random.split(key, 6)
    return {"wq": _he(k[0], (d, d)), "wk": _he(k[1], (d, d)),
            "wv": _he(k[2], (d, d)),
            "mlp": _mlp(k[3], [f * d] + [int(x) for x in cfg["mlp"]])}


def table_ids(batch):
    return {"sparse": batch["ids"]}


def embed(rows, local_ids, batch, cfg):
    """Per-field bags (B, F, d): bag[b, f] = sum over the instance's ids in
    field f of mask * row."""
    ids = local_ids["sparse"]
    B, nnz = ids.shape
    F = int(cfg["n_fields"])
    table = rows["sparse"]
    emb = table[ids.reshape(-1)] * batch["mask"].reshape(-1, 1)
    seg = (jnp.arange(B)[:, None] * F + batch["field_ids"]).reshape(-1)
    bags = jax.ops.segment_sum(emb, seg, num_segments=B * F)
    return bags.reshape(B, F, table.shape[1])


def _mlp_apply(layers, x, num):
    for i, layer in enumerate(layers):
        x = num.einsum("bi,ij->bj", x, layer["w"]) + layer["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def forward(dense, emb, batch, cfg, num):
    """Logits (B,) of the field self-attention tower; every product goes
    through ``num`` (see ``harness.refstep.Numerics``)."""
    B, F, d = emb.shape
    H = int(cfg["attn_heads"])
    hd = d // H
    proj = lambda w: num.einsum("bfi,ij->bfj", emb, w).reshape(B, F, H, hd)
    q, k, v = proj(dense["wq"]), proj(dense["wk"]), proj(dense["wv"])
    s = num.einsum("bfhd,bghd->bhfg", q, k) / hd ** 0.5
    p = jax.nn.softmax(s, axis=-1)
    o = num.einsum("bhfg,bghd->bfhd", p, v).reshape(B, F, d)
    return _mlp_apply(dense["mlp"], (emb + o).reshape(B, F * d), num)[:, 0]


def forward_flops(cfg) -> float:
    """Matmul and bag FLOPs of one instance's forward pass."""
    d, F, nnz = int(cfg["embed_dim"]), int(cfg["n_fields"]), int(
        cfg["nnz_per_instance"])
    H = int(cfg["attn_heads"])
    sizes = [F * d] + [int(x) for x in cfg["mlp"]]
    proj = 3 * F * 2 * d * d
    attn = 2 * (2 * H * F * F * (d // H))
    mlp = sum(2 * a * b for a, b in zip(sizes, sizes[1:]))
    bag = 2 * nnz * d
    return float(proj + attn + mlp + bag)
