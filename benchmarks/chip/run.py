"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload ctr-gather-mb1k --seed 7 \\
        --seconds 30 --trace 0

Set-up builds the cell's trainer through the launcher's flags, drives its
first steps for the check and warms every program past the first k-step
merge; the window then runs ``repro.runtime.online.fit_online`` for
``--seconds``.  ``--trace 0`` reports the end-to-end metrics, ``--trace 1``
the per-layer ones from a profiler trace of a shorter window.  After the
window the program's state is freed and the plain float32 reference
follows the first steps; each compared number is printed beside its limit
on standard error, and the last line of standard output is the result.

Exits non-zero, printing no result, where JAX finds no accelerator or
fewer chips than the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

TRACE_SECONDS = 4.0     # the traced window: the trace of a longer one is
                        # tens of MB on the many-table configuration


def require_chips(n: int):
    """The devices of the run, or exit non-zero: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: JAX found no accelerator (platform "
                         f"{devices[0].platform!r}); no result")
    if len(devices) < n:
        raise SystemExit(f"run.py: the cell asks for {n} chips, JAX found "
                         f"{len(devices)}; no result")
    return devices[:n]


def metric(value, unit):
    return {"value": value, "unit": unit}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import manifest

    cell = manifest.resolve(manifest.load(), args.workload)
    devices = require_chips(cell.chips)

    import jax
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from harness import peaks, tracing
    from harness.window import Run

    peak = peaks.peak(devices[0].device_kind)
    run = Run(cell.config, cell.mix, cell.model, args.seed,
              trace=bool(args.trace), t_process=T_PROCESS)
    run.setup()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    try:
        res = run.window(seconds, trace_dir)
        red = tracing.reduce(tracing.load(trace_dir)) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    mem = run.memory_stats()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem["peak_bytes"]}
    ctx = types.SimpleNamespace(
        trace=red, steps=res["steps"], instances=res["instances"],
        window_s=res["window_s"], batch=int(cell.mix["batch"]),
        distinct=res["distinct"], counters0=run.counters0,
        counters1=run.counters1, cfg=cell.config, mix=cell.mix,
        model=cell.model, peak=peak, chips=cell.chips)
    run.release()
    ok, nums, lines, _ = run.check(cell.config["limits"])

    iv = res["intervals_ms"]
    merge = iv[res["merge_step"]]
    slow = [{"step": int(res["step_nums"][i]), "ms": float(iv[i]),
             "feed_wait_ms": float(res["feed_wait_ms"][i])}
            for i in np.argsort(iv)[::-1][:3]]
    print(f"window: {res['steps']} steps in {res['window_s']!r} s; step ms "
          f"median {float(np.median(iv))!r} p95 {float(np.percentile(iv, 95))!r}"
          f" max {float(iv.max())!r}; merge steps {len(merge)} median "
          f"{float(np.median(merge)) if len(merge) else float('nan')!r}; "
          f"memory {json.dumps(mem)}; compiles in window "
          f"{res['window_compiles']}; set-up s {json.dumps(run.setup_phases)}"
          f"; slowest steps {json.dumps(slow)}", file=sys.stderr)
    if args.trace:
        metrics = {}
        for x in cell.per_layer:
            v = cell.readers[x["name"]].read(ctx)
            if v is not None:
                metrics[x["name"]] = metric(float(v), x["unit"])
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    else:
        values = {"setup_s": res["setup_s"],
                  "train_instances_per_s": res["instances"] / res["window_s"],
                  "step_ms_p95": float(np.percentile(iv, 95)),
                  "peak_hbm_gib": mem["peak_bytes"] / 2**30}
        metrics = {x["name"]: metric(values[x["name"]], x["unit"])
                   for x in cell.end_to_end}
    out = {"correct": bool(ok), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if args.trace:
        out["breakdown"] = tracing.breakdown(red)
    out["checks"] = {k: {"value": v, "limit": cell.config["limits"][k]}
                     for k, v in nums.items()}
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
