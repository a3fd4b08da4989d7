"""Device idle gaps put down to the program's own host spans.

The program opens spans named ``repro.*`` on its hot path
(``repro.runtime.spans``), on the profiler's clock.  ``load`` reads a
trace keeping them beside the benchmark's ``bench.*`` spans, and
``split`` gives, over the ``bench.window`` span:

- for each device, the interval the correction from its clock to the
  host's lies in, from causality.  A program cannot start on the device
  before the host span that launched it starts: the n-th
  ``jit__ids_from_batch_traced``, ``jit__pull``, ``jit_train`` and
  ``jit__predict_traced`` module is paired with the n-th span that
  launches it, and the interval's lower end is the least shift that puts
  every paired module after its span's start (0 where none is paired).  A wait cannot return
  before the program it waits on ends: the n-th ``jit__predict_traced``
  is paired with the n-th ``repro.predict.fetch``, and each
  ``repro.online.log`` (its fetch of the loss) with the train program
  the last train launch before it launched; the upper end is the most
  shift that keeps every paired module's end before its wait returns
  (none where no wait is paired).  Where the trace's edges cut
  off a program or span, the pairs are shifted to the alignment whose
  bound lies nearest 0; what was paired, and what could not be, is listed;
- each idle gap of each device, put down at both ends of the interval to
  the innermost span the main thread was in for most of the gap: at each
  level the gap goes to the child span that overlaps it most, unless the
  parent's own time outside its children overlaps it more; the window's
  own time is ``host:outside_spans``.  A gap whose span differs between
  the ends is unresolved;
- host seconds per program span, clipped to the window, and per step
  (a step runs from one ``repro.online.next_batch`` to the next).

Busy time, program and op sums stay ``harness.tracing``'s: the clock
correction moves only the attribution.  The readings are the functions
at the end; each returns None where the trace holds no program span, and
an idle share read at the upper end None where that end is unknown.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
from typing import Dict, List, Optional, Tuple

from harness import tracing

PROGRAM = "repro."
KEEP = ("bench.", PROGRAM)
OUTSIDE = "host:outside_spans"
STEP = "repro.online.next_batch"
LAUNCHES = {"jit__ids_from_batch_traced": ("repro.train.ids",),
            "jit__pull": ("repro.train.pull",),
            "jit_train": ("repro.train.launch", "repro.train.launch_merge"),
            "jit__predict_traced": ("repro.predict.launch",)}
WAITS = {"jit__predict_traced": ("repro.predict.fetch",)}
LOG_WAIT = "repro.online.log"
LO, HI = 0, 1       # the ends of the clock correction's interval
EDGE = 2            # programs or spans a trace's edges may cut off
DISPATCH_IDLE = ("repro.predict.", "repro.train.")
LOOP_IDLE = ("repro.online.",)
DISPATCH_HOST = ("repro.predict.stage", "repro.predict.launch",
                 "repro.train.")


def load(trace_dir: str) -> tracing.Trace:
    """The trace under ``trace_dir``: device ops and programs as
    ``tracing.load`` keeps them, host spans named ``bench.*`` or
    ``repro.*`` per thread."""
    import jax

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    devices, host = {}, {}
    for plane in data.planes:
        if tracing.DEVICE.match(plane.name):
            lines = {ln.name: [(e.name, e.start_ns, e.end_ns)
                               for e in ln.events]
                     for ln in plane.lines if ln.name in tracing.LINES}
            if lines.get("XLA Ops"):
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                spans = [(e.name, e.start_ns, e.end_ns) for e in ln.events
                         if e.name.startswith(KEEP)]
                if spans:
                    host[f"{plane.name}/{ln.name}#{i}"] = spans
    return tracing.Trace(devices, host)


def bench_only(trace: tracing.Trace) -> tracing.Trace:
    """``trace`` with the benchmark's spans alone, as ``tracing.load``
    would have read it."""
    host = {t: [x for x in evs if x[0].startswith("bench.")]
            for t, evs in trace.host.items()}
    return tracing.Trace(trace.devices, {t: e for t, e in host.items() if e})


@dataclasses.dataclass
class _Node:
    name: str
    start: float
    end: float
    children: List["_Node"]


def _tree(spans: List[tracing.Ev]) -> List[_Node]:
    """The spans of one thread as a forest (a thread's spans nest)."""
    roots: List[_Node] = []
    stack: List[_Node] = []
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        node = _Node(name, s, e, [])
        while stack and stack[-1].end <= s:
            stack.pop()
        (stack[-1].children if stack else roots).append(node)
        stack.append(node)
    return roots


def _overlap(n: _Node, lo: float, hi: float) -> float:
    return max(min(n.end, hi) - max(n.start, lo), 0.0)


def label(gap: Tuple[float, float], roots: List[_Node],
          starts: List[float]) -> str:
    """The innermost span holding most of ``gap`` (host clock);
    ``starts`` are the roots' starts."""
    lo, hi = gap
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    j = bisect.bisect_left(starts, hi)
    nodes, total, name = roots[i:j], hi - lo, OUTSIDE
    while nodes:
        ovs = [(_overlap(n, lo, hi), n) for n in nodes]
        best, node = max(ovs, key=lambda x: x[0])
        if best <= 0 or best < total - sum(o for o, _ in ovs):
            break
        name = node.name
        nodes, total = node.children, best
    if name == OUTSIDE:
        return name
    return "host:" + (name if name.startswith(PROGRAM)
                      else name[len("bench."):])


@dataclasses.dataclass
class Offset:
    """Nanoseconds to add to a device's clock to put it on the host's: the
    interval causality leaves, and the programs paired for each end."""
    lo_ns: float
    hi_ns: Optional[float]              # None: no wait paired
    paired: Dict[str, List[int]]        # "program<-span" -> [pairs,
    #                                     programs, spans in the trace]
    unpaired: Dict[str, List[int]]      # "program<-span" -> [programs, spans]

    def to_json(self) -> dict:
        us = lambda ns: None if ns is None else ns * 1e-3
        return {"lo_us": us(self.lo_ns), "hi_us": us(self.hi_ns),
                "paired": self.paired, "unpaired": self.unpaired}


def _pairs(dev, host, bound):
    """The n-th program with the (n + k)-th span.  Where the trace's edges
    cut off up to ``EDGE`` of either, k runs over what the counts allow and
    the pairs whose ``bound`` lies nearest 0 are kept: the clocks are meant
    to agree, and a wrong k is off by about a step."""
    d = len(host) - len(dev)
    best = []
    if dev and host and abs(d) <= EDGE:
        for k in range(min(d, 0), max(d, 0) + 1):
            got = [(dev[i], host[i + k]) for i in range(len(dev))
                   if 0 <= i + k < len(host)]
            if not best or abs(bound(got)) < abs(bound(best)):
                best = got
    return best


def clock_offset(lines: Dict[str, List[tracing.Ev]],
                 spans: List[tracing.Ev]) -> Offset:
    """The interval of the correction for the device whose trace lines are
    ``lines``, from the main thread's ``spans``."""
    mods = sorted(lines.get("XLA Modules", []), key=lambda x: x[1])
    paired, unpaired = {}, {}

    def pair(program, names, side, pick):
        dev = [(s, e) for n, s, e in mods if tracing.module_name(n) == program]
        host = sorted((s, e) for n, s, e in spans if n in names)
        bound = lambda pairs: pick(h[side] - d[side] for d, h in pairs)
        pairs = _pairs(dev, host, bound)
        key = f"{program}<-{'|'.join(names)}"
        if pairs:
            paired[key] = [len(pairs), len(dev), len(host)]
            return pairs, [bound(pairs)]
        unpaired[key] = [len(dev), len(host)]
        return [], []

    lows, highs, trains = [], [], []
    for program, names in LAUNCHES.items():
        pairs, got = pair(program, names, 0, max)
        lows += got
        if program == "jit_train":
            trains = pairs
    for program, names in WAITS.items():
        highs += pair(program, names, 1, min)[1]
    # the log's fetch of the loss waits on the train program that the
    # last train launch span before it launched
    starts = [h[0] for _, h in trains]
    logs = [(s, e) for n, s, e in spans if n == LOG_WAIT]
    waits = [e - trains[i][0][1] for s, e in logs
             for i in [bisect.bisect_left(starts, s) - 1] if i >= 0]
    if waits:
        paired[f"jit_train<-{LOG_WAIT}"] = [len(waits), len(trains),
                                             len(logs)]
        highs.append(min(waits))
    return Offset(max(lows, default=0.0), min(highs, default=None),
                  paired, unpaired)


@dataclasses.dataclass
class Split:
    window_s: float
    offset: Dict[str, Offset]                   # device -> clock correction
    gaps: Dict[str, List[Tuple[float, str, Optional[str]]]]
    #                     device -> (s, label at the lower end, at the upper)
    host_s: Dict[str, float]                    # program span -> seconds
    steps: List[Dict[str, float]]               # per step: span -> seconds

    @property
    def has_program_spans(self) -> bool:
        return bool(self.host_s)

    @property
    def hi_known(self) -> bool:
        return all(o.hi_ns is not None for o in self.offset.values())

    def idle_s(self, end: int = LO) -> Dict[str, float]:
        """Idle seconds per label at one end of the interval, averaged
        over the devices ({} at an unknown upper end)."""
        if end == HI and not self.hi_known:
            return {}
        out = collections.Counter()
        for gaps in self.gaps.values():
            for g in gaps:
                out[g[1 + end]] += g[0] / len(self.gaps)
        return dict(out)

    def unresolved_s(self) -> Optional[float]:
        """Idle seconds whose span differs between the interval's ends,
        averaged over the devices (None at an unknown upper end)."""
        if not self.hi_known:
            return None
        return sum(s for gaps in self.gaps.values() for s, a, b in gaps
                   if a != b) / len(self.gaps)


def split(trace: tracing.Trace) -> Split:
    (lo, hi), spans = tracing._main_spans(trace)
    roots = _tree(spans)
    starts = [n.start for n in roots]
    at = lambda a, b, off: None if off is None else label(
        (a + off, b + off), roots, starts)
    offsets, gaps = {}, {}
    for plane, lines in sorted(trace.devices.items()):
        off = offsets[plane] = clock_offset(lines, spans)
        ops = tracing._clip(lines.get("XLA Ops", []), lo, hi)
        busy = tracing.union((s, e) for _, s, e in ops)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps[plane] = [((b - a) * 1e-9, at(a, b, off.lo_ns),
                        at(a, b, off.hi_ns))
                       for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    prog = [x for x in tracing._clip(spans, lo, hi)
            if x[0].startswith(PROGRAM)]
    host_s = collections.Counter()
    for n, s, e in prog:
        host_s[n] += (e - s) * 1e-9
    marks = sorted(s for n, s, _ in prog if n == STEP)
    steps = []
    for a, b in zip(marks, marks[1:]):
        per = collections.Counter()
        for n, s, e in prog:
            if e > a and s < b:
                per[n] += (min(e, b) - max(s, a)) * 1e-9
        steps.append(dict(per))
    return Split((hi - lo) * 1e-9, offsets, gaps, dict(host_s), steps)


def busiest(step: Dict[str, float]) -> Optional[str]:
    """The program span a step spent most of its host time in."""
    return max(step, key=step.get) if step else None


# ------------------------------------------------------------- readings
def _idle_share(sp: Split, prefixes, end: int) -> Optional[float]:
    if not sp.has_program_spans or (end == HI and not sp.hi_known):
        return None
    got = sum(s for lab, s in sp.idle_s(end).items()
              if lab.startswith(tuple("host:" + p for p in prefixes)))
    return 100.0 * got / sp.window_s


def dispatch_idle_share(sp: Split, end: int = LO) -> Optional[float]:
    """% of the window the device sat idle while the host was in the
    trainer's staging, launches or fetch (``repro.predict.*``,
    ``repro.train.*``), at one end of the clock correction."""
    return _idle_share(sp, DISPATCH_IDLE, end)


def loop_idle_share(sp: Split, end: int = LO) -> Optional[float]:
    """% of the window the device sat idle while the host was in
    ``fit_online``'s own work (``repro.online.*``), at one end of the
    clock correction."""
    return _idle_share(sp, LOOP_IDLE, end)


def dispatch_ms(sp: Split, steps: int) -> Optional[float]:
    """Host ms per step in the trainer's staging and launches, busy
    device or not (the fetch, a wait on the device, left out)."""
    if not sp.has_program_spans or not steps:
        return None
    return 1e3 * sum(s for n, s in sp.host_s.items()
                     if n.startswith(DISPATCH_HOST)) / steps


def staged_kib_per_step(bytes0: Optional[float], bytes1: Optional[float],
                        steps: int) -> Optional[float]:
    """Host-to-device KiB per step ``HybridTrainer._stage`` shipped, from
    its cumulative ``staged_bytes`` before and after the window."""
    if bytes0 is None or bytes1 is None or not steps:
        return None
    return (bytes1 - bytes0) / steps / 1024.0
