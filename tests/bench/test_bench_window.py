"""The run loop on the CPU at a small size: the window's counts, no
compile inside it, a sound run checks correct, and the control and each
planted fault in the timed path check not correct.

The small configuration keeps the cell's structure (2 pods, k-step Adam
with a merge inside set-up, the launcher's flags) at CPU widths.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, faults, manifest, refstep, system, traffic
from harness.window import Run, seeds

M = manifest.load()
SEED = 2**33 + 12345        # run seeds may exceed 32 bits


def files(config, mix_name):
    """The configuration, mix and reference as their files name them."""
    cfg = json.loads((manifest.BENCH_DIR / "configs" / f"{config}.json")
                     .read_text())
    model = manifest.load_module(manifest.reference_file(cfg["reference"]),
                                 f"test_ref_{cfg['reference']}")
    return cfg, traffic.load(manifest.traffic_file(mix_name)), model


def small(config, mix_name):
    """The configuration and mix as files name them, at CPU widths."""
    cfg, mix, model = files(config, mix_name)
    if cfg["reference"] == "ctr":
        cfg.update(rows=4000, embed_dim=16, n_fields=8, nnz_per_instance=20,
                   attn_heads=2, mlp=[32, 1])
        cap = 2048
    else:
        cfg.update(rows=[300] * 26, embed_dim=16, bot_mlp=[13, 32, 16],
                   top_mlp=[64, 32, 1])
        cap = 128
    if cfg["deployment"].get("cache_rows"):
        # over three batches' distinct ids (about 770 each of 2,560), as
        # the cell's cache is over many: no step of the check would evict
        # a row a step trained, were it not for set-up's fill
        cfg["deployment"]["cache_rows"] = 2400
    cfg["deployment"].update(capacity=cap, fused_kernels="off", k=5,
                             warmup_steps=6)
    return cfg, dict(mix, batch=128), model


GATHER = ("baidu-ctr-1of512", "ctr-zipf1.1-b1k")
CACHED = ("baidu-ctr-1of512-cached", "ctr-zipf1.1-b1k")


def run_cell(case, seconds=0.5, plant=None, monkeypatch=None):
    cfg, mix, model = small(*case)
    if plant is not None:
        build = system.build

        def broken(*a, **kw):
            tr = build(*a, **kw)
            plant(tr)
            return tr

        monkeypatch.setattr(system, "build", broken)
    run = Run(cfg, mix, model, SEED)
    run.setup()
    res = run.window(seconds)
    run.release()
    ok, nums, lines, _ = run.check(cfg["limits"])
    return ok, nums, res, cfg, mix


@pytest.mark.parametrize("case", [
    GATHER, CACHED, ("dlrm-mlperf-1of32", "dlrm-zipf1.1-b2k")],
    ids=lambda c: c[0])
def test_sound_run(case):
    ok, nums, res, cfg, mix = run_cell(case)
    assert res["steps"] > 0
    ids = 128 * (cfg.get("nnz_per_instance") or 26)
    assert res["attempted"] == res["steps"] * ids
    assert res["instances"] == res["steps"] * mix["batch"]
    assert res["failed"] == 0
    assert res["window_compiles"] == []
    assert len(res["intervals_ms"]) == res["steps"]
    assert ok, nums


def frozen_state(tr):
    def train_step(batch):
        tr.step_num += 1
        return jnp.float32(0.69)
    tr.train_step = train_step


def half_batch(tr):
    loss = tr._loss

    def half(dense, emb, batch, predict=False):
        if predict:
            return loss(dense, emb, batch, predict=True)
        n = emb.shape[0] // 2
        return loss(dense, emb[:n], {k: v[:n] for k, v in batch.items()})
    tr._loss = half


def altered_answer(tr):
    predict = tr.predict

    def wrong(batch):
        s = np.array(predict(batch))
        s[0] = 1.0 - s[0]
        return s
    tr.predict = wrong


@pytest.mark.parametrize("plant, case", [
    pytest.param(f, case, id=f.__name__ + suffix)
    for suffix, case in (("", GATHER), ("-cached", CACHED))
    for f in (frozen_state, half_batch, altered_answer)] + [
    pytest.param(faults.lost_spill, CACHED, id="lost_spill-cached")])
def test_planted_fault_is_not_correct(plant, case, monkeypatch):
    ok, nums, *_ = run_cell(case, seconds=0.2, plant=plant,
                            monkeypatch=monkeypatch)
    assert not ok, nums


def test_lost_spill_is_seen_through_the_fill(monkeypatch):
    """The write-back of evicted rows is in the check because of set-up's
    fill: with no fill, no step of the check evicts a trained row, and a
    lost write-back reads correct."""
    monkeypatch.setattr(system, "fill_cache", lambda tr, batches: 0)
    ok, nums, *_ = run_cell(CACHED, seconds=0.2, plant=faults.lost_spill,
                            monkeypatch=monkeypatch)
    assert ok, nums


@pytest.mark.parametrize("plant", list(faults.MERGE_FAULTS.values()),
                         ids=list(faults.MERGE_FAULTS))
def test_planted_merge_fault_is_not_correct(plant, monkeypatch):
    """A k-step merge that departs from Algorithm 2 fails ``merge_gap``
    while every number of the first steps, before it, still passes."""
    ok, nums, *_ = run_cell(GATHER, seconds=0.2, plant=plant,
                            monkeypatch=monkeypatch)
    lim = json.loads((manifest.BENCH_DIR / "configs" / f"{GATHER[0]}.json")
                     .read_text())["limits"]
    assert not ok and nums["merge_gap"] > lim["merge_gap"], nums
    assert all(nums[k] <= lim[k] for k in nums if k != "merge_gap"), nums


@pytest.mark.parametrize("case, rows, batch", [
    pytest.param(GATHER, 30000, 1024, id="ctr-gather-mb1k-30000-1024"),
    pytest.param(CACHED, 30000, 1024,
                 id="baidu-ctr-1of512-cached-30000-1024"),
    pytest.param(("dlrm-mlperf-1of32", "dlrm-zipf1.1-b2k"), [2000] * 26, 512,
                 id="dlrm-gather-b2048-rows1-512")])
def test_control_is_not_correct(case, rows, batch):
    """The reference one step below the configuration's precision, in the
    program's place, against the float32 reference, under the cell's own
    limits: at the published widths, on a slice of rows the CPU holds."""
    cfg, mix, model = files(*case)
    cfg = dict(cfg, rows=rows)
    mix = dict(mix, batch=batch)
    wseed, dseed = seeds(SEED)
    stream = traffic.batches(mix, cfg, dseed)
    first = [next(stream) for _ in range(3)]
    ref = refstep.run(model, cfg, wseed, first)
    got = refstep.as_program(refstep.run(
        model, cfg, wseed, first,
        numerics=refstep.control_numerics(cfg)), cfg)
    ok, lines = check.verdict(check.numbers(got, ref, cfg), cfg["limits"])
    assert not ok, lines


def test_compile_in_window_is_counted(monkeypatch):
    """A program first compiled inside the window fails the run."""
    def recompile_each_step(tr):
        step = tr.train_step

        def train_step(batch):
            loss = step(batch)
            if tr.step_num > 7:
                jax.jit(lambda x: x + tr.step_num)(1.0)   # a new program
            return loss
        tr.train_step = train_step

    ok, nums, res, *_ = run_cell(GATHER, seconds=0.2,
                                 plant=recompile_each_step,
                                 monkeypatch=monkeypatch)
    assert res["window_compiles"]
    assert nums["window_compiles"] > 0 and not ok


def test_cache_fill_leaves_a_full_cache_and_no_reading_changed(monkeypatch):
    """Set-up's fill takes every cache slot after step 1, so the steps
    after it evict, and its last pulls write back rows step 1 trained; it
    changes no value, so the program reads what it reads with no fill,
    bit for bit."""
    cfg, mix, model = small(*CACHED)
    tr = system.build(cfg, mix["batch"], 7)
    pulls = system.fill_cache(tr, traffic.batches(mix, cfg, 8))
    (state,) = tr.backend_state.values()
    assert pulls > 0 and bool(np.all(np.asarray(state.slot_uid) >= 0))
    evicted = system.counters(tr)["cache_evictions"]  # the last pull's
    tr.train_step(next(traffic.batches(mix, cfg, 9)))
    assert system.counters(tr)["cache_evictions"] > evicted

    filled = Run(cfg, mix, model, SEED)
    filled.setup()
    filled.release()
    monkeypatch.setattr(system, "fill_cache", lambda tr, batches: 0)
    empty = Run(cfg, mix, model, SEED)
    empty.setup()
    empty.release()
    assert filled.fill_pulls > 0 and empty.fill_pulls == 0
    assert filled.fill_spilled_bytes > 0 and empty.fill_spilled_bytes == 0
    for k, v in empty.prog.items():
        for a, b in zip(jax.tree.leaves(v), jax.tree.leaves(filled.prog[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=k)


@pytest.mark.parametrize("case, trained", [
    pytest.param(GATHER, True, id="no-cache"),
    pytest.param(CACHED, False, id="cached-nothing-dirty"),
    pytest.param(CACHED, True, id="cached-after-a-step")])
def test_fill_stops(case, trained):
    """The fill pulls nothing where there is no cache.  With no trained
    row to evict it pulls to its cap; after a step it stops at the first
    pull past full that writes back nothing more, having written back
    rows the step trained."""
    cfg, mix, model = small(*case)
    tr = system.build(cfg, mix["batch"], 7)
    if trained:
        tr.train_step(next(traffic.batches(mix, cfg, 9)))
    before = system.counters(tr)
    pulls = system.fill_cache(tr, traffic.batches(mix, cfg, 8))
    d = cfg["deployment"]
    if not d.get("cache_rows"):
        assert pulls == 0
        return
    (state,) = tr.backend_state.values()
    assert bool(np.all(np.asarray(state.slot_uid) >= 0))
    most = 8 * -(-d["cache_rows"] // d["capacity"])
    wrote = (system.counters(tr)["cache_bytes_d2h"]
             - before["cache_bytes_d2h"])
    if trained:
        assert 0 < pulls < most and wrote > 0
    else:
        assert pulls == most and wrote == 0
