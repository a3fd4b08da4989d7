"""Unit + property tests for the k-step Adam optimizer (Algorithm 2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.kstep import KStepAdam, KStepConfig, pod_replicate, pod_consensus_error
from repro.optim.adam import Adam


def tree_allclose(a, b, atol=1e-6):
    return all(
        np.allclose(x, y, atol=atol)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def make_problem(seed=0, n_pod=1):
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32),
              "b": jnp.asarray(rng.standard_normal(3), jnp.float32)}
    return pod_replicate(params, n_pod)


def grads_like(params, seed):
    rng = np.random.default_rng(seed)
    return jax.tree.map(lambda x: jnp.asarray(rng.standard_normal(x.shape), jnp.float32), params)


def test_n1_k1_matches_reference_adam():
    """k-step Adam with one worker and k=1 must equal plain Adam exactly."""
    pp = make_problem(n_pod=1)
    opt = KStepAdam(KStepConfig(lr=0.01, b1=0.9, k=1), n_pod=1)
    ref = Adam(lr=0.01, b1=0.9)
    st_k = opt.init(pp)
    st_r = ref.init(pp)
    p_k, p_r = pp, pp
    for i in range(5):
        g = grads_like(pp, i)
        p_k, st_k = opt.step(p_k, g, st_k)
        p_r, st_r = ref.step_fn(p_r, g, st_r)
        assert tree_allclose(p_k, p_r), f"divergence at step {i}"


def test_merge_restores_consensus():
    pp = make_problem(n_pod=4)
    opt = KStepAdam(KStepConfig(lr=0.05, k=3), n_pod=4)
    state = opt.init(pp)
    p = pp
    for i in range(1, 7):
        g = jax.tree.map(
            lambda x: jnp.arange(4.0).reshape((4,) + (1,) * (x.ndim - 1)) * jnp.ones_like(x),
            pp,
        )
        p, state = opt.step(p, g, state)
        err = float(pod_consensus_error(p))
        if i % 3 == 0:
            assert err < 1e-10, f"step {i}: consensus error {err} after merge"
        else:
            assert err > 1e-8, f"step {i}: replicas should diverge locally"


def test_v_hat_is_averaged_at_merge():
    """Algorithm 2 line 12: the shared denominator becomes mean_i v_local."""
    pp = make_problem(n_pod=2)
    opt = KStepAdam(KStepConfig(lr=0.01, k=2), n_pod=2)
    state = opt.init(pp)
    p = pp
    g1 = jax.tree.map(lambda x: jnp.ones_like(x) * jnp.array([1.0, 3.0]).reshape((2,) + (1,) * (x.ndim - 1)), pp)
    p, state = opt.step(p, g1, state)            # local
    v_loc = jax.tree.leaves(state.v_local)[0]
    p, state = opt.step(p, g1, state)            # merge at t=2
    v_hat = jax.tree.leaves(state.v_hat)[0]
    v_loc2 = jax.tree.leaves(state.v_local)[0]
    expect = np.mean(np.asarray(v_loc2), axis=0)
    assert np.allclose(np.asarray(v_hat)[0], expect, atol=1e-7)
    assert np.allclose(np.asarray(v_hat)[1], expect, atol=1e-7)


def test_static_vs_dynamic_merge_identical():
    pp = make_problem(n_pod=3)
    cfg = KStepConfig(lr=0.02, k=2, b1=0.5)
    o1, o2 = KStepAdam(cfg, 3), KStepAdam(cfg, 3)
    s1, s2 = o1.init(pp), o2.init(pp)
    p1 = p2 = pp
    for i in range(4):
        g = grads_like(pp, i)
        p1, s1 = o1.step(p1, g, s1)                       # lax.cond path
        p2, s2 = o2.step(p2, g, s2, merge=((i + 1) % 2 == 0))  # static path
        assert tree_allclose(p1, p2)


def test_identical_workers_match_single_worker():
    """If all pods see the same gradients, k-step == single-worker Adam."""
    p1 = make_problem(n_pod=1)
    p4 = make_problem(n_pod=4)
    cfg = KStepConfig(lr=0.01, k=3, b1=0.0)
    o1, o4 = KStepAdam(cfg, 1), KStepAdam(cfg, 4)
    s1, s4 = o1.init(p1), o4.init(p4)
    for i in range(6):
        g1 = grads_like(p1, i)
        g4 = jax.tree.map(lambda x: jnp.broadcast_to(x[0:1], (4,) + x.shape[1:]) + 0.0,
                          pod_replicate(jax.tree.map(lambda y: y[0], g1), 4))
        g4 = jax.tree.map(lambda x: jnp.concatenate([x[:1]] * 4), g4)
        g1_ = g1
        p1, s1 = o1.step(p1, g1_, s1)
        g4 = jax.tree.map(lambda a, b: jnp.broadcast_to(a, b.shape) + jnp.zeros_like(b),
                          g1, p4)
        p4, s4 = o4.step(p4, g4, s4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        assert np.allclose(a[0], b[0], atol=1e-6)
        assert np.allclose(b[0], b[3], atol=1e-6)


def rosenbrock_like(x):
    return jnp.sum((x[..., 1:] - x[..., :-1] ** 2) ** 2 + (1 - x[..., :-1]) ** 2)


@pytest.mark.parametrize("k", [1, 5, 20])
def test_kstep_converges_nonconvex(k):
    """Convergence on a non-convex problem for several k (Theorem 1 regime)."""
    n_pod = 4
    x0 = pod_replicate({"x": jnp.zeros(8)}, n_pod)
    opt = KStepAdam(KStepConfig(lr=0.05, k=k, b1=0.9), n_pod=n_pod)
    state = opt.init(x0)
    p = x0
    key = jax.random.key(0)
    T = 400

    def pod_loss(px, noise):
        return rosenbrock_like(px["x"] + noise)

    @jax.jit
    def step(p, state, key):
        key, sub = jax.random.split(key)
        noise = jax.random.normal(sub, (n_pod, 8)) * 0.05
        g = jax.grad(
            lambda pp: jnp.sum(jax.vmap(lambda px, nz: pod_loss(px, nz))(pp, noise))
        )(p)
        p, state = opt.step(p, g, state)
        return p, state, key

    for t in range(T):
        p, state, key = step(p, state, key)
    final = rosenbrock_like(jnp.mean(jax.tree.leaves(p)[0], axis=0))
    assert float(final) < 0.5, f"k={k}: did not converge, f={float(final)}"


@settings(max_examples=20, deadline=None)
@given(
    n_pod=st.integers(1, 5),
    k=st.integers(1, 8),
    steps=st.integers(1, 16),
    b1=st.sampled_from([0.0, 0.9]),
)
def test_property_kstep_invariants(n_pod, k, steps, b1):
    """Properties that must hold for any (n_pod, k, b1, steps):
    - after a merge step: consensus error == 0 and v_hat == mean(v_local);
    - between merges: v_hat unchanged (frozen shared denominator);
    - all states remain finite."""
    pp = make_problem(seed=n_pod * 7 + k, n_pod=n_pod)
    opt = KStepAdam(KStepConfig(lr=0.03, k=k, b1=b1), n_pod=n_pod)
    state = opt.init(pp)
    p = pp
    prev_vhat = state.v_hat
    for i in range(1, steps + 1):
        g = grads_like(pp, seed=100 + i)
        p, state = opt.step(p, g, state)
        is_merge = i % k == 0
        if is_merge:
            assert float(pod_consensus_error(p)) < 1e-9
            for vh, vl in zip(jax.tree.leaves(state.v_hat), jax.tree.leaves(state.v_local)):
                mean_vl = np.mean(np.asarray(vl), axis=0)
                for pod in range(n_pod):
                    assert np.allclose(np.asarray(vh)[pod], mean_vl, rtol=1e-5)
        else:
            assert tree_allclose(state.v_hat, prev_vhat)
        prev_vhat = state.v_hat
        for leaf in jax.tree.leaves(p) + jax.tree.leaves(state.m):
            assert np.all(np.isfinite(leaf))


def test_grad_clip():
    pp = make_problem(n_pod=2)
    opt = KStepAdam(KStepConfig(lr=0.1, k=1, grad_clip=0.5), n_pod=2)
    state = opt.init(pp)
    g = jax.tree.map(lambda x: jnp.ones_like(x) * 100.0, pp)
    p1, _ = opt.step(pp, g, state)
    # with clipping, the effective |g| per pod is <= 0.5 -> bounded update
    delta = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(p1), jax.tree.leaves(pp)))
    assert delta < 10.0


def test_delayed_merge_blend():
    pp = make_problem(n_pod=2)
    snap = pp
    merged = jax.tree.map(lambda x: x * 0.0 + 1.0, pp)
    now = jax.tree.map(lambda x: x + 0.25, pp)
    out = KStepAdam.apply_delayed_merge(now, snap, merged)
    for leaf in jax.tree.leaves(out):
        assert np.allclose(leaf, 1.25, atol=1e-6)
