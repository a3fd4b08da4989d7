"""One run of one cell: set-up, the measured window, the readings, and
the check.  ``run.py`` drives it on the chip; the tests drive it on the
CPU at a small size.

Set-up builds the trainer once and hands that same object to the window.
Its first steps go through the window's own call (``fit_online``) and
feed, on the mix's first batches (all distinct); the program's readings
for the check are taken between them: the rows those batches touch before
step 1, after step 1 and after step ``REF_STEPS``, the dense tower and
Adam's first moment, the losses and the served scores; then the dense
tower right before the first k-step merge, and the tower and the moments
right after it.  Where the placement has a cache tier, set-up fills it
right after step 1 with pulls of the mix's traffic drawn from a seed of
its own (``system.fill_cache``), which change no value: the cache is full
from step 2 on, and the fill's last pulls evict rows that step 1 trained,
so the readings after step 1 and the steps after it read those rows back
through the cache tier's write-back.  Warm-up runs past the merge, so
every program the window runs has compiled before it opens.  The program
runs at the matmul precision its configuration states
(``matmul_precision``; JAX's ``jax_default_matmul_precision``).
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Callable, Optional

import jax
import numpy as np

from harness import check, refstep, system, traffic
from harness.feed import Feed

REF_STEPS = 3
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Counts the programs JAX lowers while ``active`` (every new
    executable, eager operations included, is lowered once)."""

    def __init__(self):
        self.active = False
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == COMPILE_EVENT:
            self.names.append(kw.get("fun_name", "?"))


class Instrumented:
    """The trainer as ``fit_online`` sees it: host spans around ``predict``
    and ``train_step``, and each step's loss (a device scalar) and served
    scores kept for the check.  Everything else is the trainer's own."""

    def __init__(self, trainer, span: Callable[[str], object]):
        self._t = trainer
        self._span = span
        self.losses: list = []
        self.scores: list = []
        self.keep_scores = 0

    def __getattr__(self, name):
        return getattr(self._t, name)

    def predict(self, batch):
        with self._span("bench.predict"):
            s = self._t.predict(batch)
        if self.keep_scores > 0:
            self.scores.append(np.asarray(s, np.float32))
            self.keep_scores -= 1
        return s

    def train_step(self, batch):
        with self._span("bench.train_step"):
            loss = self._t.train_step(batch)
        self.losses.append(loss)
        return loss


def seeds(seed: int):
    """(weight seed, data seed) from the run's ``--seed`` (any size)."""
    w, d = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(w) & 0x7FFFFFFF, int(d)


def fill_seed(seed: int) -> int:
    """The seed of the stream that fills a cache tier after the first
    step: the same traffic as the run's, drawn apart from it."""
    child = np.random.SeedSequence(int(seed)).spawn(1)[0]
    return int(child.generate_state(1)[0])


def ids_per_batch(model, batch: dict) -> int:
    """Ids in ``batch`` over every table the reference ``model`` reads."""
    return sum(int(x.size) for x in model.table_ids(batch).values())


class Run:
    """Set-up, window and check of one cell for one seed."""

    def __init__(self, cfg: dict, mix: dict, model, seed: int,
                 trace: bool = False, t_process: Optional[float] = None):
        from repro.runtime.online import fit_online

        self.cfg, self.mix, self.model = cfg, mix, model
        self.trace = trace
        self._fit = fit_online
        self.t_process = (time.perf_counter() if t_process is None
                          else t_process)
        self.seed = seed
        self.wseed, self.dseed = seeds(seed)
        self.span = ((lambda n: jax.profiler.TraceAnnotation(n)) if trace
                     else (lambda n: contextlib.nullcontext()))
        self.compiles = CompileCounter()
        self.prog: dict = {}

    # ------------------------------------------------------------ set-up
    def _phase(self, name):
        now = time.perf_counter()
        self.setup_phases[name] = round(now - self._t_phase, 3)
        self._t_phase = now

    def setup(self):
        with jax.default_matmul_precision(self.cfg["matmul_precision"]):
            self._setup()

    def _setup(self):
        d = self.cfg["deployment"]
        k = int(d["k"])
        self.setup_phases = {}
        self._t_phase = self.t_process
        self._phase("start")
        self.trainer = tr = system.build(self.cfg, int(self.mix["batch"]),
                                         self.wseed)
        jax.block_until_ready(tr.tables)
        self._phase("build")
        self.feed = Feed(traffic.batches(self.mix, self.cfg, self.dseed),
                         stats=self.model if self.trace else None,
                         span=self.span)
        self.inst = inst = Instrumented(tr, self.span)
        self.first = self.feed.take(REF_STEPS)
        uids = refstep.touched(self.model, self.first)
        p = self.prog
        p["rows0"] = system.read_rows(tr, uids)
        p["dense0"] = system.dense(tr)
        self._phase("read")
        inst.keep_scores = REF_STEPS
        self.steps(self.first[:1])
        self._phase("step1")
        before = system.counters(tr)
        self.fill_pulls = system.fill_cache(
            tr, traffic.batches(self.mix, self.cfg, fill_seed(self.seed)))
        jax.block_until_ready(tr.tables)
        self.fill_spilled_bytes = (
            system.counters(tr).get("cache_bytes_d2h", 0.0)
            - before.get("cache_bytes_d2h", 0.0))
        self._phase("fill")
        p["rows1"] = system.read_rows(tr, uids)
        p["moment1"] = system.first_moment(tr)
        self.steps(self.first[1:])
        p["rows_last"] = system.read_rows(tr, uids)
        p["dense_last"] = system.dense(tr)
        p["scores"] = list(inst.scores)
        self._phase("steps2_3")
        self.steps(self.feed.take(k - 1 - tr.step_num))
        before = system.dense(tr)
        self.steps(self.feed.take(1))                 # the first merge
        p["merge"] = dict(system.merge_state(tr), before=before)
        self._phase("to_merge")
        warm = max(int(d["warmup_steps"]) - tr.step_num, 1)
        self.steps(self.feed.take(warm))
        jax.block_until_ready((tr.tables, tr.dense))
        p["losses"] = [float(x) for x in jax.device_get(
            inst.losses[:REF_STEPS])]
        # what set-up made lives on: keep the collector's full passes in
        # the window from walking it, so they cost the same in every run
        gc.collect()
        gc.freeze()
        self._phase("warmup")

    def steps(self, batches):
        self._fit(self.inst, iter(batches), len(batches))

    # ------------------------------------------------------------ window
    def window(self, seconds: float, trace_dir: Optional[str] = None):
        tr, inst = self.trainer, self.inst
        self.counters0 = system.counters(tr)
        step0 = tr.step_num
        n_loss0 = len(inst.losses)
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the benchmark's spans only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.compiles.active = True
        try:
            with jax.default_matmul_precision(self.cfg["matmul_precision"]), \
                    self.span("bench.window"):
                self._fit(inst, self.feed.window(seconds), 1 << 40)
                jax.block_until_ready((tr.tables, tr.dense))
            t_end = time.perf_counter()
        finally:
            self.compiles.active = False
            if trace_dir:
                jax.profiler.stop_trace()
        stamps = np.asarray(self.feed.stamps)
        n = len(stamps) - 1
        self.counters1 = system.counters(tr)
        losses = np.asarray(jax.device_get(inst.losses[n_loss0:]))
        k = int(self.cfg["deployment"]["k"])
        step_nums = step0 + 1 + np.arange(n)
        ids = ids_per_batch(self.model, self.first[0])
        self.result = {
            "steps": n,
            "instances": n * int(self.mix["batch"]),
            "window_s": float(t_end - stamps[0]),
            "setup_s": float(stamps[0] - self.t_process),
            "intervals_ms": np.diff(stamps) * 1e3,
            "merge_step": step_nums % k == 0,
            "attempted": n * ids,
            "failed": int(self.counters1["overflow_dropped"]
                          - self.counters0["overflow_dropped"])
            + int(np.sum(~np.isfinite(losses))) * ids,
            "window_compiles": list(self.compiles.names),
            "distinct": list(self.feed.distinct),
            "feed_wait_ms": np.asarray(self.feed.waits) * 1e3,
            "step_nums": step_nums,
        }
        return self.result

    def memory_stats(self) -> dict:
        """The fullest device's peak: buffers in use plus the scratch its
        programs reserved (XLA's temporaries, such as a relayout copy of a
        table), which together bound the HBM the run needed at once."""
        best = {"peak_bytes": 0}
        for dev in system.program_devices(self.trainer):
            st = dev.memory_stats() or {}
            used = int(st.get("peak_bytes_in_use", 0))
            scratch = int(st.get("peak_bytes_reserved", 0))
            if used + scratch >= best["peak_bytes"]:
                best = {"peak_bytes": used + scratch,
                        "peak_bytes_in_use": used,
                        "peak_bytes_reserved": scratch}
        return best

    def release(self):
        """Stop the feed and free the program's state."""
        self.feed.close()
        self.trainer = self.inst = None
        gc.unfreeze()
        gc.collect()

    # ------------------------------------------------------------- check
    def check(self, limits: dict, ref: Optional[dict] = None):
        """Compare the program's readings with the float32 reference's
        (``ref``: the reference's readings for this seed, if made)."""
        if ref is None:
            ref = refstep.run(self.model, self.cfg, self.wseed, self.first)
        prog = dict(self.prog)
        prog["window_compiles"] = len(self.result["window_compiles"]) \
            if hasattr(self, "result") else 0
        nums = check.numbers(prog, ref, self.cfg)
        ok, lines = check.verdict(nums, limits)
        return ok, nums, lines, ref
