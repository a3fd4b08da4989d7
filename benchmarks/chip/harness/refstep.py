"""Plain reference of the training loop the timed path runs: the model's
reference (``benchmarks/chip/reference/<name>.py``) under the trainer's
algorithm, written out in ``jax.numpy``.

Per step, as ``HybridTrainer`` documents it: the scores of the whole
batch with pod 0's dense replica before the step (online
predict-then-train); the batch split into ``n_pod`` equal shards, one per
dense replica; the mean binary cross-entropy of each shard; the dense
gradient of each replica from its own shard; the row gradients summed over
pods and divided by ``n_pod``; a local k-step Adam step (Zhao et al.
Algorithm 2, beta1 = 0, the running local second moment before the first
merge, no bias correction); sparse AdaGrad on the rows the batch touched.

Only the rows the given batches touch are held, initialised from the seed
as the program's factory documents: table ``i`` (in sorted name order) is
``normal(fold_in(key(seed), i), (rows, dim)) * std``.

``Numerics`` says how products are taken: ``exact`` float32 (precision
highest), the reference; ``bf16x3``, three bfloat16 passes as a TPU's
``high`` precision takes them: the control, one step below the
``highest`` the configurations state.  The passes are written out, so the
control reads the same on any backend.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def table_init(seed: int, index: int, rows: int, dim: int, std: float,
               uids: np.ndarray) -> jnp.ndarray:
    """Initial values of rows ``uids`` of table ``index``."""
    key = jax.random.fold_in(jax.random.key(seed), index)
    table = jax.random.normal(key, (rows, dim), jnp.float32) * std
    return jnp.take(table, jnp.asarray(uids), axis=0)


def touched(model, batches) -> Dict[str, np.ndarray]:
    """Sorted distinct ids of each table over ``batches``."""
    per = [model.table_ids(b) for b in batches]
    return {n: np.unique(np.concatenate([np.asarray(p[n]).reshape(-1)
                                         for p in per]))
            for n in per[0]}


class Numerics:
    def __init__(self, kind: str):
        if kind not in ("exact", "bf16x3"):
            raise ValueError(f"unknown numerics {kind!r}")
        self.kind = kind

    def einsum(self, spec, a, b):
        hi = jax.lax.Precision.HIGHEST
        if self.kind == "exact":
            return jnp.einsum(spec, a, b, precision=hi)

        def split(x):
            # reduce_precision, not a round trip through bfloat16, which
            # XLA may drop as excess precision (it does on a TPU)
            bf16 = lambda y: jax.lax.reduce_precision(y, 8, 7)
            h = bf16(x)
            return h, bf16(x - h)

        (ah, al), (bh, bl) = split(a), split(b)
        f = lambda x, y: jnp.einsum(spec, x, y, precision=hi)
        return f(ah, bh) + (f(ah, bl) + f(al, bh))


def control_numerics(cfg: dict) -> str:
    """The precision one step below the configuration's."""
    if cfg["matmul_precision"] != "highest":
        raise ValueError("the reference has a control for matmul precision "
                         "'highest' only")
    return "bf16x3"


def _bce(logits, labels):
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def make_step(model, cfg: dict, num: Numerics,
              half_batch: bool = False):
    """One reference step as a jitted function.  ``half_batch`` plants a
    fault for the check's own tests: each pod's loss is the mean over the
    first half of its shard only."""
    d = cfg["deployment"]
    P = int(d["n_pod"])
    lr, b1, b2 = d["lr"], d["adam_b1"], d["adam_b2"]
    slr, seps = d["sparse_lr"], d["adagrad_eps"]

    def pod_view(batch, local, p, n):
        sl = slice(p * n, (p + 1) * n)
        return ({k: x[sl] for k, x in batch.items()},
                {t: x[sl] for t, x in local.items()})

    def step(dense, rows, accum, v, batch, local):
        B = batch["label"].shape[0]
        n = B // P

        def total(dense, rows):
            losses = []
            for p in range(P):
                bp, lp = pod_view(batch, local, p, n)
                if half_batch:
                    bp, lp = pod_view(bp, lp, 0, n // 2)
                dp = jax.tree.map(lambda x: x[p], dense)
                logits = model.forward(dp, model.embed(rows, lp, bp, cfg),
                                       bp, cfg, num)
                losses.append(_bce(logits, bp["label"]))
            losses = jnp.stack(losses)
            return jnp.sum(losses), losses

        d0 = jax.tree.map(lambda x: x[0], dense)
        scores = jax.nn.sigmoid(model.forward(
            d0, model.embed(rows, local, batch, cfg), batch, cfg, num))
        (gd, gr), losses = jax.grad(total, argnums=(0, 1), has_aux=True)(
            dense, rows)
        gr = jax.tree.map(lambda g: g / P, gr)
        m = jax.tree.map(lambda g: (1 - b1) * g, gd)
        v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, v, gd)
        dense = jax.tree.map(lambda p, mm, vv: p - lr * mm / jnp.sqrt(vv),
                             dense, m, v)
        accum = jax.tree.map(lambda a, g: a + g * g, accum, gr)
        rows = jax.tree.map(lambda w, g, a: w - slr * g / (jnp.sqrt(a) + seps),
                            rows, gr, accum)
        return dense, rows, accum, v, jnp.mean(losses), scores, gd, gr

    return jax.jit(step)


def run(model, cfg: dict, seed: int, batches: List[dict],
        numerics: str = "exact", half_batch: bool = False) -> dict:
    """Follow the program through ``len(batches)`` steps (all before the
    first merge).  Returns the readings the check compares: each step's
    loss and scores, the first step's gradients, and the parameters before
    the first step and after the last."""
    d = cfg["deployment"]
    if len(batches) >= d["k"]:
        raise ValueError("the reference follows only local steps")
    P = int(d["n_pod"])
    num = Numerics(numerics)
    with jax.default_matmul_precision("highest"):
        uids = touched(model, batches)
        rows = {}
        for i, (name, n_rows, dim) in enumerate(model.tables(cfg)):
            rows[name] = table_init(seed, i, n_rows, dim,
                                    cfg["table_init_std"], uids[name])
        dense = model.init_dense(jax.random.key(seed), cfg)
        dense = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (P,) + x.shape), dense)
        f32 = lambda t: jax.tree.map(lambda x: np.asarray(x, np.float32),
                                     jax.device_get(t))
        out = {"uids": uids, "dense0": f32(dense), "rows0": f32(rows)}
        accum = jax.tree.map(
            lambda w: jnp.full(w.shape, d["initial_accumulator"]), rows)
        v = jax.tree.map(lambda x: jnp.full(x.shape, d["adam_eps"]), dense)
        step = make_step(model, cfg, num, half_batch)
        losses, scores = [], []
        for t, b in enumerate(batches):
            ids = model.table_ids(b)
            local = {n: jnp.asarray(np.searchsorted(uids[n], ids[n]),
                                    jnp.int32) for n in ids}
            batch = {k: jnp.asarray(x) for k, x in b.items()}
            dense, rows, accum, v, loss, s, gd, gr = step(
                dense, rows, accum, v, batch, local)
            losses.append(float(loss))
            scores.append(np.asarray(jax.device_get(s), np.float32))
            if t == 0:
                out["grad_dense"] = jax.device_get(gd)
                out["grad_rows"] = jax.device_get(gr)
                out["rows1"] = jax.device_get(rows)
        out.update(losses=losses, scores=scores, dense_last=f32(dense),
                   rows_last=f32(rows))
        for key in ("grad_dense", "grad_rows", "rows1"):
            out[key] = f32(out[key])
    return out


def merge(before, m, v_local, lr: float):
    """The k-step merge (Zhao et al. Algorithm 2 lines 12-13) in float64
    numpy, from each pod's tower before the merge step and the moments that
    step computed: ``v_hat = mean_i v_local_i`` and ``x = mean_i (x_i - lr
    m_i / sqrt(v_hat))``.  Returns ``(x, v_hat)``, one replica each."""
    f64 = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float64), t)
    before, m, v_local = f64(before), f64(m), f64(v_local)
    v_hat = jax.tree.map(lambda v: v.mean(axis=0), v_local)
    x = jax.tree.map(lambda p, mm, vh: (p - lr * mm / np.sqrt(vh[None]))
                     .mean(axis=0), before, m, v_hat)
    return x, v_hat


def as_program(ref: dict, cfg: dict) -> dict:
    """A reference run's readings in the form the program's take, so a
    reference in the program's place (the control, a planted fault) goes
    through the same check."""
    b1 = cfg["deployment"]["adam_b1"]
    return {"losses": ref["losses"], "scores": ref["scores"],
            "moment1": jax.tree.map(lambda g: (1 - b1) * g,
                                    ref["grad_dense"]),
            "rows0": ref["rows0"], "rows1": ref["rows1"],
            "rows_last": ref["rows_last"], "dense0": ref["dense0"],
            "dense_last": ref["dense_last"], "merge": None}
