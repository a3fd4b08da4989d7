"""From a profiler trace to the numbers the per-layer readers read.

A trace is kept as plain data: for each device, its ``XLA Ops`` and ``XLA
Modules`` events as ``(name, start_ns, end_ns)``; for the host, the
benchmark's own spans (names starting ``bench.``) per thread.  ``load``
takes that from the ``.xplane.pb`` the JAX profiler writes; ``from_json``
reads it back from JSON, which is what the tests' recorded fixture is.

``reduce`` clips everything to the host span ``bench.window`` and gives,
per device: the busy time (the union of the op intervals), the time of
each program (XLA module, by its name before ``(``), the time of each op
(by its HLO instruction name), and the idle gaps, each attributed to the
benchmark span the main thread was in for most of the gap.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re
from typing import Dict, List, Tuple

Ev = Tuple[str, float, float]            # (name, start_ns, end_ns)
DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
LINES = ("XLA Ops", "XLA Modules")
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Ev]]]   # plane -> line -> events
    host: Dict[str, List[Ev]]                 # thread line -> bench spans

    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host}


def from_json(d: dict) -> Trace:
    tup = lambda evs: [(str(n), float(s), float(e)) for n, s, e in evs]
    return Trace({p: {ln: tup(ev) for ln, ev in lines.items()}
                  for p, lines in d["devices"].items()},
                 {t: tup(ev) for t, ev in d["host"].items()})


def load(trace_dir: str) -> Trace:
    """The trace the profiler wrote under ``trace_dir``."""
    import jax

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    devices, host = {}, {}
    for plane in data.planes:
        if DEVICE.match(plane.name):
            lines = {ln.name: [(e.name, e.start_ns, e.end_ns)
                               for e in ln.events]
                     for ln in plane.lines if ln.name in LINES}
            if lines.get("XLA Ops"):
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                spans = [(e.name, e.start_ns, e.end_ns) for e in ln.events
                         if e.name.startswith("bench.")]
                if spans:       # threads can share a name: keep them apart
                    host[f"{plane.name}/{ln.name}#{i}"] = spans
    return Trace(devices, host)


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals covering ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(hlo_text: str) -> str:
    """``%pad.11 = f32[..] pad(..)`` -> ``pad.11``."""
    head = hlo_text.split(" ", 1)[0]
    return head[1:] if head.startswith("%") else head


def module_name(name: str) -> str:
    """``jit__pull(1082..)`` -> ``jit__pull``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Device:
    busy_s: float
    modules_s: Dict[str, float]       # program name -> seconds
    ops_s: Dict[str, float]           # HLO text -> seconds
    ops_n: Dict[str, int]             # HLO text -> events
    op_module: Dict[str, str]         # HLO text -> program it ran in
    gaps: List[Tuple[float, str]]     # (seconds, what the host was doing)


@dataclasses.dataclass
class Reduced:
    window_s: float
    devices: Dict[str, Device]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices.values()) / len(
            self.devices)

    def module_s(self, name: str) -> List[float]:
        """Seconds of program ``name`` on each device."""
        return [d.modules_s.get(name, 0.0) for d in self.devices.values()]

    def ops_matching(self, pred) -> List[Tuple[float, int]]:
        """(seconds, events) of the ops whose HLO text satisfies
        ``pred``, on each device."""
        out = []
        for d in self.devices.values():
            hits = [t for t in d.ops_s if pred(t)]
            out.append((sum(d.ops_s[t] for t in hits),
                        sum(d.ops_n[t] for t in hits)))
        return out


def _clip(evs: List[Ev], lo: float, hi: float) -> List[Ev]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def _main_spans(trace: Trace) -> Tuple[Tuple[float, float], List[Ev]]:
    for spans in trace.host.values():
        win = [x for x in spans if x[0] == WINDOW]
        if win:
            _, lo, hi = win[0]
            return (lo, hi), sorted(x for x in spans if x[0] != WINDOW)
    raise ValueError(f"the trace holds no host span {WINDOW!r}")


def _attribute(gap: Tuple[float, float], spans: List[Ev],
               starts: List[float]) -> str:
    lo, hi = gap
    best, label = 0.0, "host:outside bench spans"
    i = max(bisect.bisect_right(starts, lo) - 8, 0)
    for name, s, e in spans[i:]:
        if s >= hi:
            break
        ov = min(e, hi) - max(s, lo)
        if ov > best:
            best, label = ov, "host:" + name[len("bench."):]
    return label


def reduce(trace: Trace) -> Reduced:
    (lo, hi), spans = _main_spans(trace)
    starts = [s for _, s, _ in spans]
    devices = {}
    for plane, lines in sorted(trace.devices.items()):
        ops = _clip(lines.get("XLA Ops", []), lo, hi)
        mods = sorted(_clip(lines.get("XLA Modules", []), lo, hi),
                      key=lambda x: x[1])
        busy = union((s, e) for _, s, e in ops)
        modules_s = collections.Counter()
        for n, s, e in mods:
            modules_s[module_name(n)] += (e - s) * 1e-9
        mstarts = [s for _, s, _ in mods]
        ops_s, ops_n = collections.Counter(), collections.Counter()
        op_module = {}
        for n, s, e in ops:
            ops_s[n] += (e - s) * 1e-9
            ops_n[n] += 1
            if n not in op_module:
                j = bisect.bisect_right(mstarts, s) - 1
                op_module[n] = (module_name(mods[j][0])
                                if j >= 0 and mods[j][2] >= s else "?")
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = zip(edges[0::2], edges[1::2])
        gaps = [((b - a) * 1e-9, _attribute((a, b), spans, starts))
                for a, b in idle if b > a]
        devices[plane] = Device(
            busy_s=sum(e - s for s, e in busy) * 1e-9,
            modules_s=dict(modules_s),
            ops_s=dict(ops_s), ops_n=dict(ops_n), op_module=op_module,
            gaps=gaps)
    if not devices:
        raise ValueError("the trace holds no device operations")
    return Reduced(window_s=(hi - lo) * 1e-9, devices=devices)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The longest device operations and idle gaps, over all devices:
    ``{"device_ops": [[name, s], ..], "idle_gaps": [[name, s], ..]}``."""
    ops, gaps = collections.Counter(), collections.Counter()
    for d in red.devices.values():
        for text, s in d.ops_s.items():
            ops[f"{d.op_module.get(text, '?')}/{op_name(text)}"] += s
        for s, label in d.gaps:
            gaps[label] += s
    n = len(red.devices)
    return {"device_ops": [[k, v / n] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v / n] for k, v in gaps.most_common(top)]}
