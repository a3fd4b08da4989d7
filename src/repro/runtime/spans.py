"""Host spans of the online step, on the profiler's clock.

``span(name)`` is ``jax.profiler.TraceAnnotation``: a span lands in the
same profiler trace as the device's programs and operations, on the
profiler's clock, so a trace can put each device idle gap down to what the
host was doing in it.  With no trace recording, a span costs constructing
the annotation and nothing more.

Names are constant strings starting ``repro.``; a variant of a span gets a
name of its own, and no span formats its name or takes arguments per call.

  repro.online.next_batch    ``fit_online``: the stream's ``next()``
  repro.online.meter         ``fit_online``: ``StreamingAUC.update``
  repro.online.log           ``fit_online``: the ``log_every`` record
  repro.predict.stage        ``HybridTrainer.predict``: host->device batch
  repro.predict.launch       ``HybridTrainer.predict``: the scoring program
  repro.predict.fetch        scores and serve meters back to the host
  repro.train.stage          ``HybridTrainer.train_step``: host->device batch
  repro.train.ids            the ids program
  repro.train.pull           the pull program and the engine's commit
  repro.train.pod_batch      the batch split into per-pod shards
  repro.train.launch         the local train program
  repro.train.launch_merge   the k-step merge train program
"""

import jax

span = jax.profiler.TraceAnnotation
