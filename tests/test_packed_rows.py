"""Packed gather tables: several logical rows per 128-lane physical row.

With the Pallas push (here in interpret mode, ``REPRO_KERNEL_INTERPRET=1``
from conftest) a ``GatherBackend`` keeps a table whose width divides 128
lanes as ``PackedRows``.  Only where the bytes sit may change: export
returns the logical table bit for bit, pulls and lookups serve the logical
rows, and a push equals the unfused scatter on the logical table and
accumulator bit for bit.  A width of 128 keeps the row layout untouched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.embedding_backend import GatherBackend, PackedRows
from repro.core.sparse_optim import SparseAdagrad, SparseAdagradConfig

ROWS = 41            # not a multiple of 2 or 4: the last physical row is part pad
CAP = 12
# whole groups (0-1 at uids[0], 6-7), lone rows (9, 40), a group that is
# whole for f = 2 and partial for f = 4 (12-13 | 14), and 8 distinct ids in
# a capacity of 12, so four capacity pads repeat uids[0]
IDS = [0, 1, 6, 7, 9, 12, 13, 14, 40, 7, 0, 13]
# more distinct ids than the capacity: the largest are dropped
OVERFLOW = [3, 2, 5, 4, 39, 38, 20, 21, 22, 23, 24, 25, 26, 30, 31, 32]


def _tables(dim, seed=0):
    rng = np.random.default_rng(seed + dim)
    table = jnp.asarray(rng.standard_normal((ROWS, dim)), jnp.float32)
    accum = jnp.asarray(rng.random((ROWS, dim)) + 0.05, jnp.float32)
    return table, accum, rng


def _bitwise(a, b, msg):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, msg
    assert np.array_equal(a, b), msg


@pytest.mark.parametrize("dim", [64, 32, 128])
def test_prepare_export_roundtrip(dim):
    table, accum, _ = _tables(dim)
    gb = GatherBackend(fused=True)
    for x in (table, accum):
        p = gb.prepare(x)
        if dim == 128:
            assert p is x and gb.export(p) is x
            continue
        f = 128 // dim
        assert isinstance(p, PackedRows)
        assert p.data.shape == (-(-ROWS // f), 128)
        _bitwise(gb.export(p), x, "export(prepare(x)) == x")
        # logical row r sits in lanes (r % f) * dim of physical row r // f
        r = ROWS - 1
        _bitwise(p.data[r // f, (r % f) * dim:(r % f + 1) * dim], x[r],
                 "row placement")


@pytest.mark.parametrize("dim", [64, 32, 128])
@pytest.mark.parametrize("ids", [IDS, OVERFLOW], ids=["groups", "overflow"])
def test_pull_lookup_push_match_unfused(dim, ids):
    """Pull and lookup serve the logical gather; the fused push on packed
    rows, exported, equals the unfused scatter on logical rows bit for bit,
    and the counters count the rows whose write was folded."""
    table, accum, rng = _tables(dim)
    opt = SparseAdagrad(SparseAdagradConfig(lr=0.1))
    ids = jnp.asarray(ids, jnp.int32)
    gb, ub = GatherBackend(fused=True), GatherBackend()
    pt, pa = gb.prepare(table), gb.prepare(accum)
    st = gb.init_state(pt)

    ws, pt, pa, st = gb.pull(pt, pa, st, ids, CAP)
    wsu, _, _, _ = ub.pull(table, accum, (), ids, CAP)
    assert ws.rows.shape == (CAP + 1, dim)
    _bitwise(ws.rows, wsu.rows, "pulled rows")
    _bitwise(ws.uids, wsu.uids, "uids")
    looked, aux = gb.lookup(pt, pa, st, ids, CAP)
    _bitwise(looked.rows, wsu.rows, "lookup rows")
    assert float(aux["serve_lookups"]) == ids.size - int(wsu.n_dropped)

    # pads carry zero gradient (the pipeline invariant); the drop row not
    u = np.asarray(wsu.uids)
    live = np.r_[True, u[1:] != u[0]]
    g = rng.standard_normal((CAP + 1, dim)).astype(np.float32)
    g[:CAP][~live] = 0.0
    g = jnp.asarray(g)
    nt, na, st = gb.push(pt, pa, st, ws, g, opt)
    ut, ua, _ = ub.push(table, accum, (), wsu, g, opt)
    _bitwise(gb.export(nt), ut, "pushed table")
    _bitwise(gb.export(na), ua, "pushed accum")

    if dim == 128:
        assert st == () and gb.stats(st) == {}
        return
    distinct = u[live]
    merged = len(distinct) - len(np.unique(distinct // (128 // dim)))
    assert merged > 0
    assert gb.stats(st) == {"packed_merged": float(merged),
                            "packed_pushed": float(len(distinct))}


def test_trainer_reports_packed_group_share():
    """Through the trainer: ``sparse_metrics`` reports the share of pushed
    distinct rows whose write was folded, per interval and in total."""
    from repro.core.embedding_engine import EmbeddingEngine, TableSpec
    from repro.core.kstep import KStepConfig
    from repro.data import synthetic as S
    from repro.models import recsys as R
    from repro.runtime.trainer import HybridTrainer, TrainerConfig

    cfg = R.CTRConfig(rows=512, n_fields=4, nnz_per_instance=8, mlp=(16, 1),
                      attn_heads=2)
    engine = EmbeddingEngine(
        {"sparse": TableSpec("sparse", rows=cfg.rows, dim=cfg.embed_dim,
                             id_field="ids")},
        capacity=256, backend=GatherBackend(fused=True))
    tr = HybridTrainer(
        R.ctr_init_dense(jax.random.key(0), cfg), engine,
        R.ctr_embed_from_workings(cfg), R.ctr_hybrid_loss(cfg),
        TrainerConfig(n_pod=1, kstep=KStepConfig(k=1)), rng=jax.random.key(1))
    assert isinstance(tr.tables["sparse"], PackedRows)
    gen = S.ctr_batches(seed=2, batch=16, rows=cfg.rows,
                        n_fields=cfg.n_fields, nnz=cfg.nnz_per_instance)
    merged = pushed = 0
    for _ in range(2):
        batch = next(gen)
        tr.train_step(batch)
        u = np.unique(batch["ids"])
        merged += len(u) - len(np.unique(u // 2))
        pushed += len(u)
    m = tr.sparse_metrics()
    assert m["packed_group_share"] == pytest.approx(merged / pushed)
    assert m["packed_group_share_total"] == m["packed_group_share"]
