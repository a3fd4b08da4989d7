"""Layer-2 trace audit: each check catches a seeded violation (callback,
f64 widening, missing donation, retrace, implicit transfer) and passes on a
real trainer; plus the ``fit_online(strict_transfers=True)`` runtime gate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.trace_audit import (
    audit_recsys,
    audit_serve_decode,
    audit_serve_lookup,
    callback_primitives,
    donation_marked,
    f64_leaks,
)


# ----------------------------------------------- seeded-violation detection
def test_callback_check_catches_host_round_trip():
    """A step that smuggles host code in via pure_callback is caught."""

    def bad_step(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x,
        )
        return jnp.sum(y)

    jx = jax.make_jaxpr(bad_step)(jnp.ones((4,), jnp.float32))
    assert callback_primitives(jx) == ["pure_callback"]


def test_callback_check_clean_on_pure_step():
    jx = jax.make_jaxpr(lambda x: jnp.sum(x * 2))(jnp.ones((4,)))
    assert callback_primitives(jx) == []


def test_callback_check_recurses_into_scan():
    def bad_scan(x):
        def body(c, _):
            c = jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct((), x.dtype), c
            )
            return c, c
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    jx = jax.make_jaxpr(bad_scan)(jnp.float32(1.0))
    assert "pure_callback" in callback_primitives(jx)


def test_f64_check_catches_widening():
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(lambda x: x * 2.0)(np.float64(1.0))
    assert f64_leaks(jx) != []


def test_f64_check_clean_at_f32():
    jx = jax.make_jaxpr(lambda x: x * 2.0)(jnp.float32(1.0))
    assert f64_leaks(jx) == []


def test_donation_check_sees_donor_marking():
    x = jnp.ones((8,))
    donated = jax.jit(lambda a: a + 1, donate_argnums=(0,)).lower(x).as_text()
    plain = jax.jit(lambda a: a + 1).lower(x).as_text()
    assert donation_marked(donated)
    assert not donation_marked(plain)


def test_retrace_detection_via_cache_size():
    """_cache_size() growth is how the audit sees a silent recompile."""
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((2,)))
    size0 = f._cache_size()
    f(jnp.ones((2,)))              # same signature: no growth
    assert f._cache_size() == size0
    f(jnp.ones((3,)))              # new shape: the seeded retrace
    assert f._cache_size() == size0 + 1


def test_transfer_guard_trips_on_implicit_h2d():
    """A raw numpy operand mixed into a device op is an implicit per-step
    host->device transfer — the runtime check's seeded violation."""
    y = jax.jit(lambda x: x * 2)(jnp.ones((4,)))
    host = np.ones((4,), np.float32)
    with pytest.raises(Exception, match="[Dd]isallow"):
        with jax.transfer_guard("disallow"):
            _ = y + host


def test_transfer_guard_passes_explicit_put():
    y = jax.jit(lambda x: x * 2)(jnp.ones((4,)))
    with jax.transfer_guard("disallow"):
        _ = y + jax.device_put(np.ones((4,), np.float32))


# ------------------------------------------------------- real-trainer audit
@pytest.mark.parametrize("placement", ["gather", "routed"])
def test_audit_recsys_clean(placement):
    """One real arch x placement passes every check (ctr exercises the
    multi-hot bag path; routed exercises the mesh-committed state fix)."""
    results = audit_recsys("baidu-ctr", placement)
    failed = [(r.check, r.detail) for r in results if not r.ok]
    assert failed == []
    assert {r.check for r in results} == {
        "callback", "f64", "donation", "retrace", "transfer-sync"}


def test_audit_serve_decode_clean():
    results = audit_serve_decode()
    failed = [(r.check, r.detail) for r in results if not r.ok]
    assert failed == []


def test_audit_serve_lookup_clean():
    """The co-located CTR serving tier passes its audit: clean jaxpr, NO
    donation of the live training buffers it shares with the trainer, one
    compiled executable across drains, and a transfer-guard-clean
    interleaved train+serve loop."""
    results = audit_serve_lookup()
    failed = [(r.check, r.detail) for r in results if not r.ok]
    assert failed == []
    assert {r.check for r in results} == {
        "callback", "f64", "no-donation", "retrace", "transfer-sync"}


# --------------------------------------------------- fit_online strict gate
class _SyncingTrainer:
    """Train loop double whose step mixes a HOST numpy array into a device
    op — the implicit-transfer bug strict_transfers must catch."""

    class cfg:
        log_every = 10_000

    def __init__(self):
        self.step_num = 0
        self.history = []
        self.ckpt = None
        self._w = jax.jit(lambda x: x * 2)(jnp.ones((4,)))

    def train_step(self, batch):
        self.step_num += 1
        self._w = self._w + batch["dense"]          # implicit h2d of numpy
        return jnp.sum(self._w)


def _np_batches(n):
    for _ in range(n):
        yield {"dense": np.ones((4,), np.float32)}


def test_fit_online_strict_catches_implicit_transfer():
    from repro.runtime.online import fit_online

    with pytest.raises(Exception, match="[Dd]isallow"):
        fit_online(_SyncingTrainer(), _np_batches(3), steps=3,
                   strict_transfers=True)


def test_fit_online_lenient_allows_it():
    from repro.runtime.online import fit_online

    hist, auc = fit_online(_SyncingTrainer(), _np_batches(3), steps=3)
    assert auc is None


def test_fit_online_strict_real_trainer():
    """The production loop survives the guard end to end: staging is
    explicit device_put, metrics materialize via explicit device_get."""
    from repro import configs
    from repro.analysis.trace_audit import _build_recsys
    from repro.data import synthetic as S
    from repro.runtime.online import fit_online

    tr = _build_recsys("baidu-ctr", "gather", False)
    gen = S.recsys_batches(configs.get("baidu-ctr").smoke_cfg,
                           batch=32, seed=3)
    hist, auc = fit_online(tr, gen, steps=4, strict_transfers=True)
    assert tr.step_num == 4
    assert auc is not None
