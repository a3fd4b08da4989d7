"""Readings that the check's limits are set from, for one cell.

    python3 benchmarks/chip/calibrate.py --workload ctr-gather-mb1k \\
        --seeds 11 12 13 ... --control-seeds 11 12 13 \\
        --faults no_merge merge_copies_pod0 --fault-seeds 21 22 23

For each ``--seeds`` seed: the program's set-up (the trainer at the
cell's size, its first steps and the first k-step merge, as a run makes
them) against the float32 reference: the lower readings.  For each
``--control-seeds`` seed, with no program: the reference computed one
step below the configuration's matmul precision in the program's place
(the control, ``refstep.control_numerics``), and the float32 reference
with half of each pod's shard left out of the loss (a planted fault),
each against the float32 reference: the upper readings.  For each
``--faults`` name and each ``--fault-seeds`` seed, the program with that
fault planted (``harness.faults.FAULTS``): a merge fault
(``MERGE_FAULTS``) reads its ``merge_gap`` alone, any other every number
against the reference, which a ``--seeds`` seed's program run shares.
One JSON line per reading.  The program's runs need the cell's chips, as a run does;
the control alone needs one.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[],
                    help="faults to plant (harness.faults)")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from harness import manifest
    from run import require_chips

    cell = manifest.resolve(manifest.load(), args.workload)
    require_chips(cell.chips if args.seeds or args.faults else 1)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from harness import check, faults, refstep, system, traffic
    from harness.window import Run, seeds

    limits = cell.config["limits"]
    build = system.build

    def planted(fault):
        def broken(*a, **kw):
            tr = build(*a, **kw)
            faults.FAULTS[fault](tr)
            return tr
        return broken

    refs = {}                     # seed -> the reference's readings
    for fault, seed in ([(None, s) for s in args.seeds]
                        + [(f, s) for f in args.faults
                           for s in args.fault_seeds]):
        kind = "program" if fault is None else f"fault_{fault}"
        system.build = build if fault is None else planted(fault)
        try:
            run = Run(cell.config, cell.mix, cell.model, seed)
            run.setup()
        finally:
            system.build = build
        run.release()
        if fault in faults.MERGE_FAULTS:
            # a merge fault changes the merge alone, and merge_gap needs
            # no reference run: skip the first steps' reference
            ml = check.merge_leaves(run.prog["merge"], cell.config)
            print(json.dumps({"kind": kind, "seed": seed,
                              "merge_gap": max(ml.values()),
                              "merge_leaves": ml,
                              "setup": run.setup_phases}), flush=True)
            continue
        t0 = time.perf_counter()
        ok, nums, _, ref = run.check(limits, refs.get(seed))
        refs[seed] = ref
        print(json.dumps({"kind": kind, "seed": seed, "correct": ok,
                          **nums, "check_s": time.perf_counter() - t0,
                          "losses": run.prog["losses"],
                          "ref_losses": ref["losses"],
                          "readings": check.readings(run.prog, ref,
                                                     cell.config),
                          "setup": run.setup_phases}), flush=True)
    cfg = cell.config
    for seed in args.control_seeds:
        wseed, dseed = seeds(seed)
        stream = traffic.batches(cell.mix, cfg, dseed)
        first = [next(stream) for _ in range(3)]
        ref = refs.get(seed) or refstep.run(cell.model, cfg, wseed, first)
        for kind, kw in (("control", {"numerics":
                                      refstep.control_numerics(cfg)}),
                         ("fault_half_batch", {"half_batch": True})):
            got = refstep.as_program(
                refstep.run(cell.model, cfg, wseed, first, **kw), cfg)
            nums = check.numbers(got, ref, cfg)
            ok, _ = check.verdict(nums, limits)
            print(json.dumps({"kind": kind, "seed": seed, "correct": ok,
                              **nums,
                              "readings": check.readings(got, ref, cfg)}),
                  flush=True)


if __name__ == "__main__":
    main()
