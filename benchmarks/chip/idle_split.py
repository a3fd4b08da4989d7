"""Where one cell's device idle time goes, by the program's host spans.

    python3 benchmarks/chip/idle_split.py --workload dlrm-gather-b2048 \\
        --seed 7 [--fixture out.json]

Set-up as ``run.py``'s; then, on the same trainer, an untraced window and
a traced one, each of ``run.py``'s traced length.  The trace is read with
the program's ``repro.*`` spans (``harness.hostspans``) and the last line
of standard output is one JSON object: the interval of the host-to-device
clock correction and the programs paired for it; the device's idle seconds
per innermost host span at each end of the interval, and the seconds whose
span differs between the ends; host ms per step in each program span; the
readings ``dispatch_idle_share`` and ``loop_idle_share`` at each end,
``dispatch_ms`` and ``staged_kib_per_step``; the slowest steps with the
span each spent most of its host time in; both windows' instances per
second.  ``--fixture`` writes one ordinary step of
the traced window (not a merge or logging step) as a recorded trace for the
tests.  Nothing is checked against the reference: ``run.py`` does that.

Exits non-zero, printing no result, where JAX finds no accelerator.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

HLO_CHARS = 200     # op text kept in a fixture: name, shape, op, target


def one_step(trace, res, run, cell, offset_ns):
    """One ordinary step of the traced window as a fixture: the window
    runs from the step's ``next()`` to 10 us after its train program."""
    from harness import hostspans, tracing

    (lo, hi), spans = tracing._main_spans(trace)
    marks = sorted(s for n, s, _ in spans
                   if n == hostspans.STEP and lo <= s <= hi)
    k = int(cell.config["deployment"]["k"])
    every = run.trainer.cfg.log_every
    nums = res["step_nums"]
    i = next(j for j in range(len(nums) // 2, len(nums) - 1)
             if nums[j] % k and nums[j] % every)
    a = marks[i]
    plane = sorted(trace.devices)[0]
    b = min(e for n, s, e in trace.devices[plane]["XLA Modules"]
            if tracing.module_name(n) == "jit_train"
            and s + offset_ns >= a) + 10e3
    cut = lambda evs, chars=None: [[n[:chars], s, e] for n, s, e in evs
                                   if e > a and s < b and n != tracing.WINDOW]
    host = {}
    for t, evs in trace.host.items():
        kept = cut(evs)
        if any(n == tracing.WINDOW for n, _, _ in evs):
            kept.insert(0, [tracing.WINDOW, a, b])
        if kept:
            host[t] = kept
    return {"steps": 1, "step": int(nums[i]),
            "batch": int(cell.mix["batch"]),
            "distinct": [res["distinct"][i]],
            "staged_bytes": (res["staged1"] - res["staged0"]) / res["steps"],
            "trace": {"devices": {p: {ln: cut(evs, HLO_CHARS)
                                      for ln, evs in lines.items()}
                                  for p, lines in trace.devices.items()},
                      "host": host}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)

    from harness import manifest
    from run import TRACE_SECONDS, require_chips

    cell = manifest.resolve(manifest.load(), args.workload)
    devices = require_chips(cell.chips)

    import jax
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from harness import hostspans, tracing
    from harness.window import Run

    run = Run(cell.config, cell.mix, cell.model, args.seed, trace=True,
              t_process=T_PROCESS)
    run.setup()
    staged = lambda: getattr(run.trainer, "staged_bytes", None)
    r = run.window(TRACE_SECONDS)
    plain = r["instances"] / r["window_s"]
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        s0 = staged()
        res = run.window(TRACE_SECONDS, trace_dir)
        res.update(staged0=s0, staged1=staged())
        trace = hostspans.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    red = tracing.reduce(hostspans.bench_only(trace))
    sp = hostspans.split(trace)
    n = res["steps"]
    ends = [sp.idle_s(hostspans.LO), sp.idle_s(hostspans.HI)]
    idle_total = sum(ends[0].values())
    labels = sorted(set(ends[0]) | set(ends[1]), key=lambda k: -max(
        ends[0].get(k, 0.0), ends[1].get(k, 0.0)))
    both = lambda read: [read(sp, hostspans.LO), read(sp, hostspans.HI)]
    iv = res["intervals_ms"]
    slow = [{"step": int(res["step_nums"][i]), "ms": float(iv[i]),
             "feed_wait_ms": float(res["feed_wait_ms"][i]),
             "span": hostspans.busiest(sp.steps[i])
             if i < len(sp.steps) else None}
            for i in np.argsort(iv)[::-1][:3]]
    out = {
        "workload": args.workload, "seed": args.seed,
        "device": devices[0].device_kind, "steps": n,
        "window_s": sp.window_s, "busy_s": red.busy_s,
        "clock_offset": {p: o.to_json() for p, o in sp.offset.items()},
        "idle_s": {k: [e.get(k, 0.0) if e else None for e in ends]
                   for k in labels},
        "idle_pct_of_idle": {k: [100.0 * e.get(k, 0.0) / idle_total
                                 if e else None for e in ends]
                             for k in labels},
        "unresolved_s": sp.unresolved_s(),
        "host_ms_per_step": {k: 1e3 * v / n
                             for k, v in sorted(sp.host_s.items())},
        "metrics": {
            "dispatch_idle_share": both(hostspans.dispatch_idle_share),
            "loop_idle_share": both(hostspans.loop_idle_share),
            "dispatch_ms": hostspans.dispatch_ms(sp, n),
            "staged_kib_per_step": hostspans.staged_kib_per_step(
                res["staged0"], res["staged1"], n)},
        "bench_idle_gaps": tracing.breakdown(red)["idle_gaps"],
        "slowest_steps": slow,
        "instances_per_s": {"untraced": plain,
                            "traced": res["instances"] / res["window_s"]},
    }
    print("host-device clock correction:", json.dumps(out["clock_offset"]),
          file=sys.stderr)
    if args.fixture:
        fx = one_step(trace, res, run, cell,
                      sp.offset[sorted(sp.offset)[0]].lo_ns)
        fx["about"] = (f"one step of {args.workload} on one "
                       f"{devices[0].device_kind}, recorded by "
                       f"benchmarks/chip/idle_split.py with the program's "
                       f"spans; HLO text cut to {HLO_CHARS} characters; the "
                       f"window ends 10 us after the step's train program")
        with open(args.fixture, "w") as f:
            json.dump(fx, f)
    run.release()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
