"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

A TPU trace holds, per chip, a plane ``/device:TPU:<n>`` whose line
``XLA Modules`` has one event per program execution (named
``jit_<fn>(<fingerprint>)``) and whose line ``XLA Ops`` has one event per
operation, kernels included (named by their HLO text,
``%flash_decode_kernel.13 = ... custom-call(...)``).  Host spans of the
benchmark (``jax.profiler.TraceAnnotation``) sit on the host plane on the
same clock.

* busy time: the union of module executions; idle share is one minus
  busy over the traced window;
* time per program: a program is told apart by its fingerprint, and its
  kind (decode, prefill, ...) by the kernels that run inside it;
* the top operations by device time, and the longest idle gaps with the
  host span each fell in.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # (start_ns, end_ns)
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: float                          # ns
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    modules: Dict[int, List[Event]]       # per device index
    ops: Dict[int, List[Event]]
    host: List[Event]                     # benchmark host spans

    @property
    def devices(self) -> List[int]:
        return sorted(self.modules)


def op_name(hlo_text: str) -> str:
    """``%flash_decode_kernel.13 = bf16[...] custom-call(...)`` ->
    ``flash_decode_kernel``: the stable part of an operation's name."""
    head = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def module_name(name: str) -> str:
    """``jit_traced(1568...)`` -> ``jit_traced``."""
    return name.split("(", 1)[0]


def load(path: str, host_names: Sequence[str]) -> Trace:
    """Read an ``.xplane.pb``: module and op events of every TPU plane, and
    the host spans whose names are in ``host_names``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return from_planes(data.planes, host_names)


def from_planes(planes, host_names: Sequence[str]) -> Trace:
    modules: Dict[int, List[Event]] = {}
    ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    wanted = set(host_names)
    for plane in planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[dev] = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                    for e in line.events]
                elif line.name == "XLA Ops":
                    ops[dev] = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in wanted)
    for dev in modules:
        ops.setdefault(dev, [])
    host.sort(key=lambda e: e.start)
    return Trace(modules=modules, ops=ops, host=host)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def window(trace: Trace, span: str) -> Interval:
    """The traced window: the benchmark's host span ``span``."""
    hits = [e for e in trace.host if e.name == span]
    if not hits:
        raise ValueError(f"no host span {span!r} in the trace")
    return hits[0].start, hits[-1].end


def busy_s(trace: Trace, win: Interval) -> float:
    """Seconds with a program running on the device, averaged over chips."""
    per = [covered(union([(e.start, e.end) for e in evs], *win))
           for evs in trace.modules.values()]
    return sum(per) / max(len(per), 1) / 1e9


def _inside(ev: Event, win: Interval) -> bool:
    return win[0] <= ev.start and ev.end <= win[1]


def programs(trace: Trace, win: Interval) -> Dict[str, dict]:
    """Per program (module name with its fingerprint), over every chip:
    executions, device seconds, and the stable names of the ops seen in
    its first execution inside the window (``ops``) — what tells a decode
    step (it runs the decode kernel) from a prefill."""
    out: Dict[str, dict] = {}
    for dev, mods in trace.modules.items():
        ops = sorted((e for e in trace.ops[dev] if _inside(e, win)),
                     key=lambda e: e.start)
        starts = [e.start for e in ops]
        for mod in (m for m in mods if _inside(m, win)):
            rec = out.get(mod.name)
            if rec is None:
                lo = bisect.bisect_left(starts, mod.start)
                hi = bisect.bisect_left(starts, mod.end)
                rec = out[mod.name] = {"count": 0, "seconds": 0.0,
                                       "ops": {op_name(e.name) for e in ops[lo:hi]}}
            rec["count"] += 1
            rec["seconds"] += mod.dur / 1e9
    return out


def runs_op(prog: dict, prefix: str) -> bool:
    return any(n.startswith(prefix) for n in prog["ops"])


def op_seconds(trace: Trace, win: Interval, prefix: str) -> Tuple[int, float]:
    """Calls and device seconds of the ops whose stable name starts with
    ``prefix`` (a kernel), over every chip."""
    evs = [e for evs in trace.ops.values() for e in evs
           if _inside(e, win) and op_name(e.name).startswith(prefix)]
    return len(evs), sum(e.dur for e in evs) / 1e9


def top_ops(trace: Trace, win: Interval, n: int = 10) -> List[list]:
    """The ``n`` operations with most device time (control-flow containers
    left out, since their time is their body's), seconds per chip."""
    tot: Dict[str, float] = {}
    for evs in trace.ops.values():
        for e in evs:
            if _inside(e, win):
                name = op_name(e.name)
                if name.split("_")[0] not in CONTAINERS and name not in CONTAINERS:
                    tot[name] = tot.get(name, 0.0) + e.dur
    chips = max(len(trace.ops), 1)
    return [[k, v / chips / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, win: Interval, n: int = 10,
              dev: Optional[int] = None) -> List[list]:
    """The ``n`` longest idle gaps of one chip, each named by the host span
    that overlaps it most (``idle`` where none does)."""
    if not trace.devices:
        return []
    dev = trace.devices[0] if dev is None else dev
    busy = union([(e.start, e.end) for e in trace.modules[dev]], *win)
    out = []
    for s, e in sorted(gaps(busy, *win), key=lambda g: g[0] - g[1])[:n]:
        best, name = 0.0, "idle"
        for h in trace.host:
            ov = min(e, h.end) - max(s, h.start)
            if ov > best and h.end - h.start < win[1] - win[0]:
                best, name = ov, h.name
        out.append([name, (e - s) / 1e9])
    return out


def host_span_seconds(trace: Trace, win: Interval, name: str) -> List[float]:
    return [e.dur / 1e9 for e in trace.host if e.name == name and _inside(e, win)]
