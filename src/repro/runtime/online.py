"""Online predict-then-train loop (paper §5 evaluation protocol).

One canonical copy of the loop the launcher and the examples used to
hand-roll: score each incoming batch with the CURRENT model, then train on
it — the production regime where every ad impression is first served, then
learned from.  Works with any trainer the factory builds:

  - ``trainer.prefetch(b)`` dispatches the batch's working-set pull before
    the predict/train pair (a no-op unless ``TrainerConfig.prefetch``;
    predictions legally read the in-flight pull's pass-through state),
  - unlabeled streams (two-tower retrieval) skip the scoring side and train
    only — ``fit_online`` then returns ``auc=None``,
  - ``strict_transfers=True`` (launcher: ``--strict-transfers``) wraps each
    predict/train pair in ``jax.transfer_guard("disallow")``: any IMPLICIT
    host<->device transfer in the hot path raises immediately with the
    offending op — the runtime arm of the ``repro.analysis`` sync audit.
    Deliberate crossings stay legal because they are explicit: batch staging
    uses ``jax.device_put``, score/loss materialization uses
    ``jax.device_get``, and checkpoint writes run in a transfer-allowed
    section.  Logging boundaries (``history_record``) run OUTSIDE the guard
    — materializing the interval's metrics there is the contract.

History records land in ``trainer.history`` exactly like ``fit``'s, plus an
``auc`` key for labeled streams.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional, Tuple

import jax

from repro.runtime import spans
from repro.runtime.metrics import StreamingAUC
from repro.runtime.trainer import history_record


def _format_record(rec: dict, steps_this_run: int) -> str:
    parts = [f"step {rec['step']:5d}", f"loss {rec['loss']:.4f}"]
    if "auc" in rec:
        parts.append(f"AUC {rec['auc']:.4f}")
    if "cache_hit_rate" in rec:
        parts.append(f"cache_hit {rec['cache_hit_rate']:.3f}")
    if rec.get("overflow_dropped", 0):
        parts.append(f"dropped {rec['overflow_dropped']}")
    # throughput of THIS run: rec["step"] is the global (resume-inclusive)
    # counter, but rec["sec"] only spans this loop
    parts.append(f"{steps_this_run / max(rec['sec'], 1e-9):.1f} steps/s")
    return "  ".join(parts)


def fit_online(
    trainer,
    batches: Iterator,
    steps: int,
    window: int = 30,
    log=None,
    strict_transfers: bool = False,
) -> Tuple[list, Optional[float]]:
    """Predict-then-train ``steps`` batches; returns ``(history, auc)``.

    ``auc`` is the streaming AUC over the last ``window`` scored batches
    (``None`` when the stream carries no labels).  ``log`` (e.g. ``print``)
    receives one formatted line per ``TrainerConfig.log_every`` boundary.
    ``strict_transfers`` fails fast on any implicit host<->device transfer
    inside the predict/train hot path (debug gate; see module docstring).
    """
    meter = StreamingAUC(window=window)
    scored = False
    loss = None
    start_step = trainer.step_num
    t0 = time.perf_counter()
    prefetch = getattr(trainer, "prefetch", None)
    guard = ((lambda: jax.transfer_guard("disallow")) if strict_transfers
             else contextlib.nullcontext)

    def _record():
        with spans.span("repro.online.log"):
            rec = history_record(trainer, loss, t0)   # fit's record schema
            if scored:
                rec["auc"] = meter.value()
            trainer.history.append(rec)
            if log:
                log(_format_record(rec, trainer.step_num - start_step))

    for _ in range(steps):
        try:
            with spans.span("repro.online.next_batch"):
                b = next(batches)
        except StopIteration:
            break   # finite stream shorter than steps: finish cleanly
        with guard():
            if prefetch is not None:
                prefetch(b)
            scores = trainer.predict(b) if "label" in b else None
            loss = trainer.train_step(b)
        if scores is not None:
            # meter update happens OUTSIDE the guard: predict() already
            # materialized scores host-side via an explicit device_get
            with spans.span("repro.online.meter"):
                meter.update(b["label"], scores)
            scored = True
        if trainer.step_num % trainer.cfg.log_every == 0:
            _record()
    if loss is not None and (
        not trainer.history or trainer.history[-1]["step"] != trainer.step_num
    ):
        _record()   # short runs (steps < log_every) still get a final record
    if trainer.ckpt:
        trainer.ckpt.wait()   # surface async-writer failures at loop exit
    return trainer.history, (meter.value() if scored else None)
