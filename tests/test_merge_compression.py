"""Merge schedules + payload compression: numerical contracts."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import merge as merge_lib
from repro.core.compression import dequantize_int8, quantize_int8, quantization_residual
from repro.core.kstep import KStepAdam, KStepConfig, pod_replicate


def test_flat_mean_correct():
    x = {"a": jnp.arange(12.0).reshape(4, 3)}
    out = merge_lib.flat_mean(x)
    expect = np.broadcast_to(np.arange(12.0).reshape(4, 3).mean(0), (4, 3))
    np.testing.assert_allclose(np.asarray(out["a"]), expect, rtol=1e-6)


def test_two_phase_equals_flat_without_mesh():
    rng = np.random.default_rng(0)
    x = {"w": jnp.asarray(rng.standard_normal((3, 8, 5)), jnp.float32)}
    a = merge_lib.flat_mean(x)
    b = merge_lib.two_phase_mean(x, mesh=None)
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-6)


@settings(max_examples=30, deadline=None)
@given(
    n_pod=st.integers(2, 6),
    n=st.integers(1, 64),
    scale=st.floats(1e-3, 1e3),
)
def test_int8_ef_error_bounded(n_pod, n, scale):
    """Quantized merge error is bounded by one quantization step, and the
    error-feedback residual exactly accounts for what was not transmitted."""
    rng = np.random.default_rng(n_pod * 31 + n)
    x = {"w": jnp.asarray(rng.standard_normal((n_pod, n)) * scale, jnp.float32)}
    ef = {"w": jnp.zeros((n_pod, n), jnp.float32)}
    merged, new_ef = merge_lib.int8_ef_mean(x, ef, mesh=None)
    true_mean = np.mean(np.asarray(x["w"]), axis=0)
    s = np.max(np.abs(np.asarray(x["w"]))) / 127.0 + 1e-30
    err = np.max(np.abs(np.asarray(merged["w"])[0] - true_mean))
    assert err <= s * n_pod + 1e-6, (err, s)
    # residuals bounded by one local quantization step
    assert np.max(np.abs(np.asarray(new_ef["w"]))) <= s * n_pod / 2 + 1e-6 + s


def test_ef_recovers_lost_mass_over_rounds():
    """With constant payload, EF-compressed merges converge to the true mean."""
    n_pod = 4
    rng = np.random.default_rng(3)
    payload = jnp.asarray(rng.standard_normal((n_pod, 32)), jnp.float32)
    ef = jnp.zeros_like(payload)
    true_mean = np.mean(np.asarray(payload), axis=0)
    acc = np.zeros(32)
    for r in range(1, 50):
        merged, ef_d = merge_lib.int8_ef_mean({"w": payload}, {"w": ef}, mesh=None)
        ef = ef_d["w"]
        acc += np.asarray(merged["w"])[0]
        # running average of transmitted means approaches the true mean
    np.testing.assert_allclose(acc / 49, true_mean, atol=2e-2)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 1000), st.floats(1e-6, 1e4))
def test_quantize_roundtrip_bound(n, scale):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    assert float(jnp.max(jnp.abs(back - x))) <= float(s) / 2 + 1e-9 + float(s) * 1e-3
    resid = quantization_residual(x, q, s)
    np.testing.assert_allclose(np.asarray(back + resid), np.asarray(x), rtol=1e-6)


def test_int8_ef_merge_inside_optimizer_converges():
    """End-to-end: quadratic optimization under int8_ef merging reaches the
    optimum (error feedback does its job)."""
    n_pod = 4
    target = jnp.asarray(np.random.default_rng(0).standard_normal(16), jnp.float32)
    pp = pod_replicate({"x": jnp.zeros(16)}, n_pod)
    opt = KStepAdam(KStepConfig(lr=0.05, k=4, merge="int8_ef"), n_pod=n_pod)
    state = opt.init(pp)
    p = pp

    @jax.jit
    def step(p, state):
        g = jax.grad(
            lambda q: jnp.sum(jax.vmap(lambda qi: jnp.sum((qi["x"] - target) ** 2))(q))
        )(p)
        return opt.step(p, g, state)

    for t in range(300):
        p, state = step(p, state)
    final = np.asarray(jax.tree.leaves(p)[0]).mean(axis=0)
    # converges to the optimum up to the int8 quantization floor (~s*n_pod)
    np.testing.assert_allclose(final, np.asarray(target), atol=0.12)
