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


def small(config, mix_name):
    """The configuration and mix as files name them, at CPU widths."""
    cfg = json.loads((manifest.BENCH_DIR / "configs" / f"{config}.json")
                     .read_text())
    model = manifest.load_module(manifest.reference_file(cfg["reference"]),
                                 f"test_ref_{cfg['reference']}")
    if cfg["reference"] == "ctr":
        cfg.update(rows=4000, embed_dim=16, n_fields=8, nnz_per_instance=20,
                   attn_heads=2, mlp=[32, 1])
        cap = 2048
    else:
        cfg.update(rows=[300] * 26, embed_dim=16, bot_mlp=[13, 32, 16],
                   top_mlp=[64, 32, 1])
        cap = 128
    cfg["deployment"].update(capacity=cap, fused_kernels="off", k=5,
                             warmup_steps=6)
    mix = dict(traffic.load(manifest.traffic_file(mix_name)), batch=128)
    return cfg, mix, model


GATHER = ("baidu-ctr-1of512", "ctr-zipf1.1-b1k")


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
    GATHER, ("dlrm-mlperf-1of32", "dlrm-zipf1.1-b2k")], ids=lambda c: c[0])
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


@pytest.mark.parametrize("plant", [frozen_state, half_batch, altered_answer],
                         ids=lambda f: f.__name__)
def test_planted_fault_is_not_correct(plant, monkeypatch):
    ok, nums, *_ = run_cell(GATHER, seconds=0.2, plant=plant,
                            monkeypatch=monkeypatch)
    assert not ok, nums


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


@pytest.mark.parametrize("workload, rows, batch", [
    ("ctr-gather-mb1k", 30000, 1024), ("dlrm-gather-b2048", [2000] * 26, 512)])
def test_control_is_not_correct(workload, rows, batch):
    """The reference one step below the configuration's precision, in the
    program's place, against the float32 reference, under the cell's own
    limits: at the published widths, on a slice of rows the CPU holds."""
    cell = manifest.resolve(M, workload)
    cfg = dict(cell.config, rows=rows)
    mix = dict(cell.mix, batch=batch)
    wseed, dseed = seeds(SEED)
    stream = traffic.batches(mix, cfg, dseed)
    first = [next(stream) for _ in range(3)]
    ref = refstep.run(cell.model, cfg, wseed, first)
    got = refstep.as_program(refstep.run(
        cell.model, cfg, wseed, first,
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
