"""Idle gaps put down to the program's host spans (``harness.hostspans``):
innermost attribution, the interval of the host-device clock correction
and the readings on hand traces; the existing reduction's numbers on the first recorded
trace, pinned; one recorded chip step of each cell with the program's
spans; ``idle_split.py`` refusing to run without an accelerator."""

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from harness import REPO_ROOT, hostspans, manifest, peaks, tracing, traffic

MS, US = 1e6, 1e3   # ns

# One predict-then-train step on the host's clock.  The device programs
# start where causality allows at the earliest: the scoring program at
# its launch span's start, the others 50 us after theirs.
HOST = [
    ["bench.window", 0.0, 30 * MS],
    ["repro.online.next_batch", 0.0, 1.2 * MS],
    ["bench.feed_wait", 0.1 * MS, 1.1 * MS],
    ["bench.predict", 1.5 * MS, 6 * MS],
    ["repro.predict.stage", 1.5 * MS, 2 * MS],
    ["repro.predict.launch", 2 * MS, 2.2 * MS],
    ["repro.predict.fetch", 2.2 * MS, 5.8 * MS],
    ["bench.train_step", 6 * MS, 14 * MS],
    ["repro.train.stage", 6 * MS, 6.5 * MS],
    ["repro.train.ids", 6.5 * MS, 6.7 * MS],
    ["repro.train.pull", 6.7 * MS, 9 * MS],
    ["repro.train.pod_batch", 9 * MS, 9.4 * MS],
    ["repro.train.launch", 9.4 * MS, 10 * MS],
    ["repro.online.meter", 14.2 * MS, 14.6 * MS],
    ["repro.online.next_batch", 15 * MS, 15.3 * MS],
]
MODULES = [["jit__predict_traced(1)", 2 * MS, 5.5 * MS],
           ["jit__ids_from_batch_traced(2)", 6.55 * MS, 6.6 * MS],
           ["jit__pull(3)", 6.75 * MS, 9 * MS],
           ["jit_reshape(4)", 9.25 * MS, 9.3 * MS],
           ["jit_train(5)", 9.45 * MS, 14.1 * MS]]
OPS = ([[f"%op.{i} = f32[1] x()", s, e] for i, (m, s, e) in
        enumerate(MODULES) if not m.startswith("jit_train")]
       + [["%fusion.1 = f32[1] fusion()", 9.45 * MS, 11 * MS],
          ["%fusion.2 = f32[1] fusion()", 13.5 * MS, 14.1 * MS],
          ["%copy.9 = f32[1] copy()", 14.6 * MS, 15 * MS]])

# (gap on the host's clock, where the host was)
GAPS = [((0.0, 2 * MS), "host:feed_wait"),            # inside next_batch
        ((5.5 * MS, 6.55 * MS), "host:repro.train.stage"),
        ((6.6 * MS, 6.75 * MS), "host:repro.train.ids"),
        ((9 * MS, 9.25 * MS), "host:repro.train.pod_batch"),
        ((9.3 * MS, 9.45 * MS), "host:repro.train.pod_batch"),
        ((11 * MS, 13.5 * MS), "host:train_step"),     # its own time
        ((14.1 * MS, 14.6 * MS), "host:repro.online.meter"),
        ((15 * MS, 30 * MS), "host:outside_spans")]


def hand(skew_ns=0.0):
    """The step with the device's clock ``skew_ns`` behind the host's."""
    shift = lambda evs: [[n, s - skew_ns, e - skew_ns] for n, s, e in evs]
    return tracing.from_json({
        "devices": {"/device:TPU:0": {"XLA Modules": shift(MODULES),
                                      "XLA Ops": shift(OPS)}},
        "host": {"main": HOST,
                 "bench-feed": [["bench.make_batch", 3 * MS, 4 * MS]]}})


def test_innermost_span_holding_most_of_each_gap():
    sp = hostspans.split(hand())
    got = sp.gaps["/device:TPU:0"]
    assert [lab for _, lab, _ in got] == [lab for _, lab in GAPS]
    assert [s for s, _, _ in got] == [pytest.approx((b - a) * 1e-9)
                                      for (a, b), _ in GAPS]
    assert sp.offset["/device:TPU:0"].lo_ns == 0.0
    assert sp.window_s == pytest.approx(0.030)


def test_correction_is_an_interval_from_launches_and_fetches():
    """The scoring program starts with its launch span (lower end 0) and
    ends 0.3 ms before its fetch returns (upper end 0.3 ms).  At the upper
    end four gaps move on by a span: they are unresolved, the rest agree."""
    sp = hostspans.split(hand())
    off = sp.offset["/device:TPU:0"]
    assert (off.lo_ns, off.hi_ns) == (0.0, pytest.approx(0.3 * MS))
    assert off.paired["jit__predict_traced<-repro.predict.fetch"] == [1, 1, 1]
    assert off.unpaired == {}
    assert off.to_json()["hi_us"] == pytest.approx(300.0)
    flips = [(s, a, b) for s, a, b in sp.gaps["/device:TPU:0"] if a != b]
    assert flips == [
        (pytest.approx(150e-6), "host:repro.train.ids", "host:repro.train.pull"),
        (pytest.approx(250e-6), "host:repro.train.pod_batch",
         "host:repro.train.launch"),
        (pytest.approx(150e-6), "host:repro.train.pod_batch",
         "host:repro.train.launch"),
        (pytest.approx(500e-6), "host:repro.online.meter", hostspans.OUTSIDE)]
    assert sp.unresolved_s() == pytest.approx(1.05e-3)
    # the trainer's gaps move between the trainer's spans: the dispatch
    # share holds at both ends; the meter's gap leaves the loop's share
    assert hostspans.dispatch_idle_share(sp, hostspans.HI) == pytest.approx(
        hostspans.dispatch_idle_share(sp, hostspans.LO))
    assert hostspans.loop_idle_share(sp, hostspans.HI) == 0.0


def test_log_fetch_of_the_loss_bounds_the_upper_end():
    """The log's fetch of the loss returns after the train program the
    last train launch before it launched: here 3 ns after the second."""
    lines = {"XLA Modules": [["jit_train(1)", 10.0, 20.0],
                             ["jit_train(1)", 40.0, 50.0]]}
    spans = [("repro.train.launch", 9.0, 11.0),
             ("repro.online.log", 30.0, 60.0),     # 40 ns after the first
             ("repro.train.launch_merge", 38.0, 39.0),
             ("repro.online.log", 45.0, 53.0)]
    off = hostspans.clock_offset(lines, spans)
    assert (off.lo_ns, off.hi_ns) == (-1.0, 3.0)
    assert off.paired["jit_train<-repro.online.log"] == [2, 2, 2]
    # a log before any train launch waits on nothing the trace holds
    assert hostspans.clock_offset(lines, spans[1:2]).hi_ns is None


def test_no_upper_end_without_a_fetch():
    trace = hand()
    trace.host["main"] = [x for x in HOST if x[0] != "repro.predict.fetch"]
    sp = hostspans.split(trace)
    off = sp.offset["/device:TPU:0"]
    assert off.hi_ns is None
    assert off.unpaired == {"jit__predict_traced<-repro.predict.fetch":
                            [1, 0]}
    assert sp.idle_s(hostspans.HI) == {}
    assert sp.unresolved_s() is None
    assert hostspans.dispatch_idle_share(sp, hostspans.HI) is None
    assert hostspans.dispatch_idle_share(sp) is not None


def test_planted_skew_flips_attribution_until_corrected():
    """150 us of skew moves the pod-split gap onto the pull span; the
    correction from causality takes it back."""
    skew = 150 * US
    trace = hand(skew)
    sp = hostspans.split(trace)
    assert sp.offset["/device:TPU:0"].lo_ns == pytest.approx(skew)
    assert [lab for _, lab, _ in sp.gaps["/device:TPU:0"]] == [
        lab for _, lab in GAPS]

    (lo, hi), spans = tracing._main_spans(trace)
    roots = hostspans._tree(spans)
    starts = [n.start for n in roots]
    planted = (9 * MS - skew, 9.25 * MS - skew)       # as the device saw it
    assert hostspans.label(planted, roots, starts) == "host:repro.train.pull"
    assert hostspans.label((planted[0] + skew, planted[1] + skew), roots,
                           starts) == "host:repro.train.pod_batch"


def test_no_correction_without_a_violation():
    # the device 50 us late everywhere: causality holds, the interval
    # holds 0 (the device may be up to 50 us behind or 250 us ahead)
    off = hostspans.split(hand(-50 * US)).offset["/device:TPU:0"]
    assert off.lo_ns == pytest.approx(-50 * US)
    assert off.hi_ns == pytest.approx(250 * US)


def test_offset_pairs_programs_with_their_launch_spans():
    lines = {"XLA Modules": [["jit_train(1)", 10.0, 20.0],
                             ["jit_train(1)", 40.0, 50.0],
                             ["jit__pull(2)", 5.0, 6.0]]}
    spans = [("repro.train.launch", 12.0, 13.0),
             ("repro.train.launch_merge", 41.0, 42.0),
             ("repro.train.pull", 1.0, 2.0)]
    # the train programs start 2 and 1 ns before their launches, the pull
    # program 4 ns after its span
    off = hostspans.clock_offset(lines, spans)
    assert off.lo_ns == 2.0 and off.hi_ns is None
    assert off.paired["jit_train<-repro.train.launch|repro.train.launch_merge"
                      ] == [2, 2, 2]
    # a launch cut off at the trace's edge: the merge launch is paired
    # with the program 1 ns from it, not the one 31 ns from it
    off = hostspans.clock_offset(lines, spans[1:])
    assert off.lo_ns == 1.0
    assert off.paired["jit_train<-repro.train.launch|repro.train.launch_merge"
                      ] == [1, 2, 1]
    # more than the edges can cut: left unpaired, and listed
    many = [("repro.train.pull", float(t), t + 1.0) for t in range(4)]
    off = hostspans.clock_offset(lines, many)
    assert off.unpaired["jit__pull<-repro.train.pull"] == [1, 4]
    assert "jit__pull<-repro.train.pull" not in off.paired


def test_host_seconds_per_span_and_per_step():
    sp = hostspans.split(hand())
    assert sp.host_s["repro.predict.fetch"] == pytest.approx(0.0036)
    assert sp.host_s["repro.online.next_batch"] == pytest.approx(0.0015)
    assert set(sp.host_s) == {n for n, _, _ in HOST
                              if n.startswith("repro.")}
    assert len(sp.steps) == 1            # from one next() to the next
    assert sp.steps[0]["repro.train.pull"] == pytest.approx(0.0023)
    assert hostspans.busiest(sp.steps[0]) == "repro.predict.fetch"
    assert hostspans.busiest({}) is None


def test_readings():
    sp = hostspans.split(hand())
    # idle in staging 1.05 ms, ids 0.15, pod split 0.25 + 0.15 of 30 ms
    assert hostspans.dispatch_idle_share(sp) == pytest.approx(
        100 * 1.6 / 30)
    assert hostspans.loop_idle_share(sp) == pytest.approx(100 * 0.5 / 30)
    # staging and launches: 0.5 + 0.2 + 0.5 + 0.2 + 2.3 + 0.4 + 0.6 ms
    assert hostspans.dispatch_ms(sp, 1) == pytest.approx(4.7)
    assert hostspans.dispatch_ms(sp, 0) is None
    assert hostspans.staged_kib_per_step(1024, 1024 + 10 * 2048, 10) == 2.0
    assert hostspans.staged_kib_per_step(None, None, 10) is None


def test_no_program_spans_reads_nothing():
    """A program without spans: the readings are None, the gaps go to the
    benchmark's spans."""
    sp = hostspans.split(hostspans.bench_only(hand()))
    assert not sp.has_program_spans
    for read in (hostspans.dispatch_idle_share, hostspans.loop_idle_share):
        assert read(sp) is None
    assert hostspans.dispatch_ms(sp, 1) is None
    labels = {lab for _, lab, _ in sp.gaps["/device:TPU:0"]}
    assert labels <= {"host:feed_wait", "host:predict", "host:train_step",
                      "host:outside_spans"}


def test_bench_only_keeps_what_tracing_load_keeps():
    t = hostspans.bench_only(hand())
    assert all(n.startswith("bench.") for evs in t.host.values()
               for n, _, _ in evs)
    assert t.devices == hand().devices


RECORDED = json.loads((Path(__file__).parent / "fixtures"
                       / "trace_ctr_gather_1step.json").read_text())

# What the seven readers read on the first recorded step, as the
# reduction of the first chip benchmark computed them.
PINNED = {"device_idle_share": 6.331424919967743,
          "step_mfu": 0.11351044094715534,
          "pull_ms": 8.151987000000002,
          "train_ms": 47.133785,
          "predict_ms": 18.306875,
          "bag_roofline": 0.3484159110074247,
          "push_roofline": 1.5850630771795824}


def _ctx(red, rec, cell):
    return types.SimpleNamespace(
        trace=red, steps=rec["steps"], instances=rec["batch"],
        window_s=red.window_s, batch=rec["batch"],
        distinct=rec["distinct"], counters0={}, counters1={},
        cfg=cell.config, mix=cell.mix, model=cell.model,
        peak=peaks.peak("TPU v5 lite"), chips=1)


def test_existing_readers_read_the_pinned_values():
    cell = manifest.resolve(manifest.load(), "ctr-gather-mb1k")
    red = tracing.reduce(tracing.from_json(RECORDED["trace"]))
    got = {n: r.read(_ctx(red, RECORDED, cell))
           for n, r in cell.readers.items()}
    assert got == PINNED


def test_recorded_step_split_agrees_with_the_reduction_rule_in_time_order():
    """Without program spans the split finds the reduction's gaps, and puts
    each where the reduction's own rule puts it when handed the spans in
    order of start.  (``tracing._main_spans`` sorts them by name, so its
    bisect and early stop miss the span that holds a gap: on this step it
    calls the 2.7 ms gap after the scoring program "outside", which the
    host spent inside ``bench.predict``.)"""
    trace = tracing.from_json(RECORDED["trace"])
    red = tracing.reduce(trace)
    sp = hostspans.split(trace)
    plane = "/device:TPU:0"
    assert [s for s, _, _ in sp.gaps[plane]] == [
        s for s, _ in red.devices[plane].gaps]
    (lo, hi), spans = tracing._main_spans(trace)
    in_time = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in in_time]
    ops = tracing._clip(trace.devices[plane]["XLA Ops"], lo, hi)
    edges = [lo] + [x for iv in tracing.union((s, e) for _, s, e in ops)
                    for x in iv] + [hi]
    rule = [tracing._attribute((a, b), in_time, starts)
            for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    assert [lab for _, lab, _ in sp.gaps[plane]] == rule
    longest = max(sp.gaps[plane], key=lambda g: g[0])
    assert longest[:2] == (pytest.approx(0.0027, abs=1e-4), "host:predict")


# One ordinary step of each cell on the chip with the program's spans
# (``idle_split.py --fixture``), and the interval of the clock correction
# in us: on the ctr step the ids program starts 876 us before its launch
# span, and the scoring program ends 2,587 us before its fetch returns.
SPAN_FIXTURES = {"ctr-gather-mb1k": ("trace_ctr_gather_1step_spans.json",
                                     (875.639, 2587.302)),
                 "dlrm-gather-b2048": ("trace_dlrm_gather_1step_spans.json",
                                       (-1010.802, 1291.861))}
# every span an ordinary step opens (no merge, no logging boundary)
STEP_SPANS = {"repro.online.next_batch", "repro.online.meter",
              "repro.predict.stage", "repro.predict.launch",
              "repro.predict.fetch", "repro.train.stage", "repro.train.ids",
              "repro.train.pull", "repro.train.pod_batch",
              "repro.train.launch"}


def _recorded(cell):
    return json.loads((Path(__file__).parent / "fixtures"
                       / SPAN_FIXTURES[cell][0]).read_text())


@pytest.mark.parametrize("cell", sorted(SPAN_FIXTURES))
def test_recorded_step_with_program_spans(cell):
    """On the chip's own clocks: every span of the step is there, the
    clock correction's interval is what causality asks, and at both of its
    ends the window's own time holds under a tenth of the device's idle
    time."""
    rec = _recorded(cell)
    sp = hostspans.split(tracing.from_json(rec["trace"]))
    assert set(sp.host_s) == STEP_SPANS
    off = sp.offset["/device:TPU:0"].to_json()
    assert (off["lo_us"], off["hi_us"]) == pytest.approx(
        SPAN_FIXTURES[cell][1], abs=1e-3)
    assert off["unpaired"] == {}
    red = tracing.reduce(tracing.from_json(rec["trace"]))
    device_idle = 100.0 * (1.0 - red.busy_s / red.window_s)
    for end in (hostspans.LO, hostspans.HI):
        idle = sp.idle_s(end)
        assert idle.get(hostspans.OUTSIDE, 0.0) < 0.1 * sum(idle.values())
        assert 0.0 < hostspans.dispatch_idle_share(sp, end) <= device_idle * (
            1 + 1e-12)          # the gaps summed in another order
        assert 0.0 <= hostspans.loop_idle_share(sp, end) <= device_idle
    assert hostspans.dispatch_ms(sp, 1) > 0.0


@pytest.mark.parametrize("cell", sorted(SPAN_FIXTURES))
def test_staged_bytes_is_the_batch_twice(cell):
    """The counter read on the chip: each batch of the mix goes to the
    device twice a step, once to score it and once to train on it."""
    c = manifest.resolve(manifest.load(), cell)
    batch = next(traffic.batches(c.mix, c.config, 1))
    per_batch = sum(x.nbytes for x in batch.values())
    assert _recorded(cell)["staged_bytes"] == 2 * per_batch


@pytest.mark.parametrize("cell", sorted(SPAN_FIXTURES))
def test_program_spans_leave_the_readers_unmoved(cell):
    """The seven readers read the same on a step with program spans as on
    the same step with the benchmark's spans alone."""
    rec = _recorded(cell)
    c = manifest.resolve(manifest.load(), cell)
    trace = tracing.from_json(rec["trace"])
    got = [{n: r.read(_ctx(tracing.reduce(t), rec, c))
            for n, r in c.readers.items()}
           for t in (trace, hostspans.bench_only(trace))]
    assert got[0] == got[1]
    assert any(v is not None for v in got[0].values())


def test_idle_split_refuses_without_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/idle_split.py", "--workload",
         "ctr-gather-mb1k", "--seed", "3000000000"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
