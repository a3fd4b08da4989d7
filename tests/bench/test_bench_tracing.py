"""The trace reduction: busy-interval union, per-program and per-kernel
sums, idle gaps attributed to the benchmark's host spans."""

import json
from pathlib import Path

import pytest

from harness import tracing

MS = 1e6   # ns

BAG = "%embedding_bag_pallas.1 = f32[8,128] custom-call(s32[3] %a), x"
PAD = "%pad.11 = f32[4,128] pad(f32[4,64] %copy.2, f32[] %c)"

HAND = tracing.from_json({
    "devices": {"/device:TPU:0": {
        "XLA Modules": [["jit__pull(1)", 1 * MS, 3 * MS],
                        ["jit_train(2)", 5 * MS, 9 * MS],
                        ["jit_train(2)", 12 * MS, 14 * MS]],
        "XLA Ops": [["%sort.1 = s32[4] sort(s32[4] %x)", 1 * MS, 3 * MS],
                    [BAG, 5 * MS, 7 * MS],
                    [PAD, 6 * MS, 9 * MS],         # overlaps the bag
                    [BAG, 12 * MS, 14 * MS],
                    [PAD, 19 * MS, 25 * MS]]}},    # runs past the window
    "host": {"main": [["bench.window", 0.0, 20 * MS],
                      ["bench.feed_wait", 0.0, 1 * MS],
                      ["bench.predict", 3 * MS, 4 * MS],
                      ["bench.train_step", 4 * MS, 12 * MS]],
             "bench-feed": [["bench.make_batch", 9 * MS, 12 * MS]]},
})


def test_busy_union_and_window():
    red = tracing.reduce(HAND)
    assert red.window_s == pytest.approx(0.020)
    # [1,3] + [5,9] + [12,14] + [19,20] = 2 + 4 + 2 + 1 ms
    assert red.busy_s == pytest.approx(0.009)


def test_program_and_kernel_sums():
    red = tracing.reduce(HAND)
    assert red.module_s("jit_train") == [pytest.approx(0.006)]
    assert red.module_s("jit__pull") == [pytest.approx(0.002)]
    bag = red.ops_matching(lambda t: "embedding_bag_pallas" in t)
    assert bag == [(pytest.approx(0.004), 2)]
    pad = red.ops_matching(lambda t: t.startswith("%pad"))
    assert pad == [(pytest.approx(0.004), 2)]    # 3 ms + 1 ms clipped


def test_idle_gaps_are_attributed_to_host_spans():
    red = tracing.reduce(HAND)
    gaps = red.devices["/device:TPU:0"].gaps
    # [0,1] feed wait, [3,5] predict 1 ms vs train_step 1 ms (first wins),
    # [9,12] train_step (the feed thread's span is not the main thread's),
    # [14,19] outside any span
    assert [round(s * 1e3, 6) for s, _ in gaps] == [1, 2, 3, 5]
    assert [label for _, label in gaps] == [
        "host:feed_wait", "host:predict", "host:train_step",
        "host:outside bench spans"]
    bd = tracing.breakdown(red)
    assert bd["idle_gaps"][0] == ["host:outside bench spans",
                                  pytest.approx(0.005)]
    assert bd["device_ops"][0][0] == "jit_train/embedding_bag_pallas.1"


def test_no_window_span_is_an_error():
    t = tracing.from_json({"devices": HAND.to_json()["devices"],
                           "host": {"main": [["bench.predict", 0, 1]]}})
    with pytest.raises(ValueError):
        tracing.reduce(t)


def test_op_and_module_names():
    assert tracing.op_name(PAD) == "pad.11"
    assert tracing.module_name("jit__predict_traced(1395)") == (
        "jit__predict_traced")


RECORDED = json.loads((Path(__file__).parent / "fixtures"
                       / "trace_ctr_gather_1step.json").read_text())


def test_recorded_chip_trace():
    """One real step of ctr-gather-mb1k: the programs and kernels the
    readers match by name are there, and every reader reads a number in
    its range from it."""
    import types

    from harness import manifest, peaks

    red = tracing.reduce(tracing.from_json(RECORDED["trace"]))
    assert 0 < red.busy_s <= red.window_s
    for program in ("jit__pull", "jit_train", "jit__predict_traced"):
        assert red.module_s(program)[0] > 0
    m = manifest.load()
    cell = manifest.resolve(m, "ctr-gather-mb1k")
    bag = cell.readers["bag_roofline"]
    push = cell.readers["push_roofline"]
    assert red.ops_matching(bag._is_kernel)[0][1] == 2      # train, predict
    assert red.ops_matching(push._is_kernel)[0][1] == 1
    ctx = types.SimpleNamespace(
        trace=red, steps=RECORDED["steps"], instances=RECORDED["batch"],
        window_s=red.window_s, batch=RECORDED["batch"],
        distinct=RECORDED["distinct"], counters0={}, counters1={},
        cfg=cell.config, mix=cell.mix, model=cell.model,
        peak=peaks.peak("TPU v5 lite"), chips=1)
    got = {name: r.read(ctx) for name, r in cell.readers.items()}
    for name in ("device_idle_share", "step_mfu", "bag_roofline",
                 "push_roofline"):
        assert 0 < got[name] < 100, (name, got[name])
    assert got["train_ms"] > got["predict_ms"] > got["pull_ms"] > 0
    assert got["pull_ms"] + got["train_ms"] + got["predict_ms"] <= (
        red.window_s * 1e3)
    labels = [k for k, _ in tracing.breakdown(red)["idle_gaps"]]
    assert labels and all(k.startswith("host:") for k in labels)


def _reader(name):
    from harness import manifest

    return manifest.load_module(manifest.metric_file(name), f"test_{name}")


def test_cache_hit_share_reads_the_window_s_counters():
    import types

    ctx = types.SimpleNamespace(
        counters0={"cache_lookups": 1000.0, "cache_fetched": 400.0},
        counters1={"cache_lookups": 3000.0, "cache_fetched": 700.0})
    # 2000 lookups in the window, 300 of them fetched from the cold tier
    assert _reader("cache_hit_share").read(ctx) == pytest.approx(85.0)
    ctx.counters0 = ctx.counters1 = {"overflow_dropped": 0.0}  # no cache
    assert _reader("cache_hit_share").read(ctx) is None


def test_cache_evictions_per_step_reads_the_window_s_counters():
    import types

    ctx = types.SimpleNamespace(
        steps=4, counters0={"cache_evictions": 500.0},
        counters1={"cache_evictions": 4500.0})
    assert _reader("cache_evictions_per_step").read(ctx) == 1000.0
    ctx.counters0 = ctx.counters1 = {"overflow_dropped": 0.0}  # no cache
    assert _reader("cache_evictions_per_step").read(ctx) is None
