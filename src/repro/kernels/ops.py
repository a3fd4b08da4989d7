"""jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels always compile through Mosaic — nothing,
``REPRO_KERNEL_INTERPRET`` included, can put them in interpret mode there.
On other backends (CPU tests) the wrappers run the kernels in interpret
mode when ``REPRO_KERNEL_INTERPRET=1`` or call the jnp oracle otherwise.

``kernel_mode()`` is the dispatch truth ("pallas" / "interpret" / "ref");
``resolve_fused()`` maps the ``TrainerConfig.fused_kernels`` tri-state
(None = auto) to a bool: fused defaults ON only on a real TPU backend.
An *explicit* fused=True elsewhere still executes — through interpret
under ``REPRO_KERNEL_INTERPRET=1`` (how the parity suite checks bits) or
through the jnp reference otherwise (bit-identical by construction) — so
the config axis is portable across backends.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.dot_interaction import dot_interaction_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.fused_adam import fused_adam_pallas
from repro.kernels.hash_map import hash_lookup_pallas
from repro.kernels.sparse_adagrad import (
    adagrad_row_updates,
    gather_rows_cached_pallas,
    sparse_adagrad_apply_pallas,
    sparse_adagrad_pallas,
)

_COMBINERS = ("sum", "mean", "sqrtn")


def kernel_mode() -> str:
    """How fused ops execute here: "pallas" | "interpret" | "ref".

    A TPU backend is always "pallas": the interpret override only selects
    how kernels run where there is no chip."""
    if jax.default_backend() == "tpu":
        return "pallas"
    if os.environ.get("REPRO_KERNEL_INTERPRET") == "1":
        return "interpret"
    return "ref"


def traced_on(mesh, fn):
    """``fn`` traced with ``mesh`` as the ambient mesh, so that the kernels
    it calls run whole on each device (``per_device``).  A no-op for no
    mesh or a one-device mesh."""
    if mesh is None or mesh.size == 1:
        return fn

    @functools.wraps(fn)
    def body(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return body


def per_device(fn):
    """XLA cannot partition a Mosaic kernel: in a program over a
    multi-device mesh (``traced_on``), run ``fn`` whole on every device,
    its inputs and outputs replicated."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _run(kernel, mode, *args, **static):
    """``kernel(*args)`` compiled (mode "pallas") or interpreted, whole on
    each device."""
    return per_device(functools.partial(
        kernel, interpret=(mode == "interpret"), **static))(*args)


def fused_default() -> bool:
    """Auto policy for ``fused_kernels=None``: on only for real Pallas.

    Deliberately NOT keyed on REPRO_KERNEL_INTERPRET — the env var selects
    how an *explicitly requested* fused op executes, it must not flip the
    whole test suite onto emulated kernels.
    """
    return jax.default_backend() == "tpu"


def resolve_fused(flag) -> bool:
    """Map the TrainerConfig/--fused-kernels tri-state to a bool."""
    return fused_default() if flag is None else bool(flag)


def embedding_bag(working, inv, seg, weights, num_bags, **kw):
    mode = kernel_mode()
    if mode == "ref":
        return ref.embedding_bag_ref(working, inv, seg, weights, num_bags)
    return _run(embedding_bag_pallas, mode, working, inv, seg, weights,
                num_bags=num_bags, **kw)


def embedding_bag_working(working, inv, seg, weights, num_bags,
                          combiner="sum"):
    """Differentiable fused gather+bag over the pulled working set.

    Forward: one kernel pass (gather + segment reduction); the combiner
    division stays outside, as the identical expression the unfused
    ``bag_from_working`` uses.  Backward: defined as the vjp of the
    unfused reference expression, so gradients match the unfused path's
    autodiff exactly — XLA DCEs the replayed forward, leaving only the
    transpose ops (gather of the bag cotangent, scatter-add into working).
    """
    if combiner not in _COMBINERS:
        raise ValueError(f"unknown combiner: {combiner!r}")
    mode = kernel_mode()
    if mode == "ref":
        return ref.embedding_bag_combiner_ref(
            working, inv, seg, weights, num_bags, combiner)

    # inv/seg are primal args (NOT closed over — closures would leak tracers
    # under vmap/grad) with float0 cotangents, as integer inputs require.
    @jax.custom_vjp
    def bag(wk, inv_, seg_, w):
        out = _run(embedding_bag_pallas, mode, wk, inv_, seg_, w,
                   num_bags=num_bags)
        if combiner != "sum":
            denom = ref.bag_combiner_denom_ref(seg_, num_bags, combiner,
                                               wk.dtype)
            out = out / denom[:, None]
        return out

    def fwd(wk, inv_, seg_, w):
        return bag(wk, inv_, seg_, w), (wk, inv_, seg_, w)

    def bwd(res, g):
        wk, inv_, seg_, w = res
        _, vjp = jax.vjp(
            lambda wk_, w_: ref.embedding_bag_combiner_ref(
                wk_, inv_, seg_, w_, num_bags, combiner),
            wk, w,
        )
        g_wk, g_w = vjp(g)
        f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        return g_wk, f0(inv_), f0(seg_), g_w

    bag.defvjp(fwd, bwd)
    return bag(working, inv, seg, weights)


def dot_interaction(feats, **kw):
    mode = kernel_mode()
    if mode == "ref":
        return ref.dot_interaction_ref(feats)
    return dot_interaction_pallas(feats, interpret=(mode == "interpret"), **kw)


def adam_defaults() -> tuple:
    """(b1, b2) single-sourced from the dense optimizer config (paper §5).

    Lazy import: kernels must stay importable without repro.core.
    """
    from repro.core.kstep import KStepConfig
    return (KStepConfig.b1, KStepConfig.b2)


def fused_adam(p, g, m, v, v_hat, lr=1e-3, b1=None, b2=None, **kw):
    if b1 is None or b2 is None:
        db1, db2 = adam_defaults()
        b1 = db1 if b1 is None else b1
        b2 = db2 if b2 is None else b2
    mode = kernel_mode()
    if mode == "ref":
        return ref.fused_adam_ref(p, g, m, v, v_hat, lr, b1, b2)
    return fused_adam_pallas(
        p, g, m, v, v_hat, lr=lr, b1=b1, b2=b2,
        interpret=(mode == "interpret"), **kw,
    )


def sparse_adagrad(rows, accum, grads, lr=0.05, eps=1e-10, **kw):
    mode = kernel_mode()
    if mode == "ref":
        return ref.sparse_adagrad_ref(rows, accum, grads, lr, eps)
    return sparse_adagrad_pallas(
        rows, accum, grads, lr=lr, eps=eps,
        interpret=(mode == "interpret"), **kw,
    )


def sparse_adagrad_apply(table, accum, uids, grads, *, lr, eps):
    """Fused push: AdaGrad row updates applied straight into the table.

    The row math runs once, outside, via :func:`adagrad_row_updates` (the
    same pinned helper the unfused ``SparseAdagrad.apply_rows`` uses), so
    the scatter — Pallas or jnp — receives identical (delta, g2) bits.
    """
    delta, g2 = adagrad_row_updates(accum[uids], grads, table.dtype,
                                    lr=lr, eps=eps)
    return scatter_row_updates(table, accum, uids, delta, g2)


def scatter_row_updates(table, accum, uids, delta, g2):
    """``table[uids] += delta``, ``accum[uids] += g2`` in place, under the
    index contract of ``sparse_adagrad_apply_pallas`` (the only repeats are
    pads of ``uids[0]`` with a zero update)."""
    mode = kernel_mode()
    if mode == "ref":
        return ref.sparse_adagrad_apply_ref(table, accum, uids, delta, g2)
    return _run(sparse_adagrad_apply_pallas, mode,
                table, accum, uids, delta, g2)


def hash_lookup(key_tab, slot_tab, slot_uid, uids):
    """Linear-probe id→slot lookup over the O(cache_rows) hash map.

    slots[i] = live cache slot of uids[i] (or -1).  Exact in every mode:
    the Pallas probe kernel and the jnp reference walk identical chains
    over identical map contents (map maintenance is shared trace-level
    jnp), so the dispatch mode can never change a hit into a miss.
    """
    mode = kernel_mode()
    if mode == "ref":
        return ref.hash_lookup_ref(key_tab, slot_tab, slot_uid, uids)
    return _run(hash_lookup_pallas, mode, key_tab, slot_tab, slot_uid, uids)


def gather_rows_cached(cache_rows, slots):
    """Fused cached pull: out[i] = cache_rows[slots[i]], with the
    hash-probe output as the kernel's index stream."""
    mode = kernel_mode()
    if mode == "ref":
        return ref.gather_rows_cached_ref(cache_rows, slots)
    return _run(gather_rows_cached_pallas, mode, cache_rows, slots)


def sparse_adagrad_cached_apply(cache_rows, cache_accum, slots, grads,
                                *, lr, eps):
    """Fused cached push: the hash-probe id→slot output drives the
    scatter's scalar-prefetch index stream directly."""
    accum_rows = gather_rows_cached(cache_accum, slots)
    delta, g2 = adagrad_row_updates(accum_rows, grads, cache_rows.dtype,
                                    lr=lr, eps=eps)
    return scatter_row_updates(cache_rows, cache_accum, slots, delta, g2)
