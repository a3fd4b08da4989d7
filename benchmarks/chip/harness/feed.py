"""The feed: a traffic stream made ahead by one producer thread, handed to
the trainer one batch per ``next()``.

The window's iterator stops at the first ``next()`` that comes
``seconds`` after its first; its timestamps give one wall interval per
step, because each step's ``predict`` fetches scores to the host and
depends on the previous step's tables.  It also keeps how long each
``next()`` waited for the producer, so that a slow step can be told
apart from a starved one.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Callable, Iterator, List, Optional

from harness import traffic

_DONE = object()


class Feed:
    def __init__(self, stream: Iterator[dict], depth: int = 3,
                 stats=None,
                 span: Optional[Callable[[str], object]] = None):
        """``stats``: the configuration's reference module, whose tables'
        distinct ids each batch records in ``distinct``; None for none."""
        self._stream = stream
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._stats = stats
        self._span = span or (lambda name: contextlib.nullcontext())
        self.distinct: List[dict] = []   # per handed-out batch, if stats
        self.stamps: List[float] = []    # each next() of the window
        self.waits: List[float] = []     # seconds each next() waited
        self._thread = threading.Thread(target=self._produce,
                                        name="bench-feed", daemon=True)
        self._thread.start()

    def _produce(self):
        try:
            for b in self._stream:
                with self._span("bench.make_batch"):
                    item = (b, None if self._stats is None
                            else traffic.distinct_rows(self._stats, b))
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except Exception as e:   # surfaced to the consumer by get()
            self._q.put((_DONE, e))

    def get(self) -> dict:
        b, st = self._q.get()
        if b is _DONE:
            raise RuntimeError("traffic generator failed") from st
        if st is not None:
            self.distinct.append(st)
        return b

    def take(self, n: int) -> List[dict]:
        return [self.get() for _ in range(n)]

    def window(self, seconds: float) -> Iterator[dict]:
        """Batches until ``seconds`` have passed since the first
        ``next()``; every ``next()`` is timestamped."""
        self.stamps, self.waits = [], []
        self.distinct = []
        while True:
            now = time.perf_counter()
            self.stamps.append(now)
            if now - self.stamps[0] >= seconds:
                return
            with self._span("bench.feed_wait"):
                b = self.get()
            self.waits.append(time.perf_counter() - now)
            yield b

    def close(self):
        """Stop the producer and wait for it."""
        self._stop.set()
        while self._thread.is_alive():
            try:
                self._q.get(timeout=0.05)
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
