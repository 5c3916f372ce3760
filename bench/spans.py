"""Reductions over what the program records itself: its host spans
(``serve.*``, opened by ``ContinuousEngine`` through ``repro.obs``) and the
device scopes its compiled programs carry (``jax.named_scope`` names in each
op's ``op_name``).

A TPU trace keeps an op's scope path as the stat ``tf_op`` of the op's event
metadata, which ``bench/xplane.py`` reads; ``load`` returns the trace with
the program's spans kept and that map.  A path reads like
``jit(traced)/vmap(di_link)/jit(_bernoulli)/add``: under ``vmap`` a scope is
wrapped, so a path component matches a scope with its wrappers taken off.

Each reduction returns None where the trace holds nothing to read: no decode
step, no program span, no scope (a program that names none).  The harness
does not call these yet: its trace keeps only ``HOST_SPANS`` and no scope.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench import readers, xplane
from bench import trace as tr

PROGRAM_SPANS = ("serve.step", "serve.submit", "serve.admit", "serve.decode",
                 "serve.sync", "serve.harvest")
SPLIT_SCOPES = ("stack_split", "stack_merge")   # copies at the link split
LINK_SCOPES = ("di_link",)                        # the link round
SCOPES = ("di_device_half", "di_link", "di_server_half", "di_head", "di_sample",
          *SPLIT_SCOPES)                          # every scope the program names
ENGINE_SPANS = ("serve.step", "serve.submit")     # the engine's own host work
WAIT_SPANS = ("serve.sync", "serve.harvest")      # the engine waiting on the device
SCOPE_STAT = "tf_op"

Scopes = Dict[str, Dict[str, str]]                # plane -> op name -> scope path

_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")


def load(path: str, host_names: Sequence[str]) -> Tuple[tr.Trace, Scopes]:
    """The trace with ``host_names`` and the program's spans kept, and each
    plane's op scopes."""
    with open(path, "rb") as f:
        scopes = xplane.metadata_stat(f.read(), SCOPE_STAT)
    return tr.load(path, (*host_names, *PROGRAM_SPANS)), scopes


def components(path: str) -> List[str]:
    """``jit(traced)/vmap(di_link)/mul`` -> ``["traced", "di_link", "mul"]``."""
    out = []
    for c in path.split("/"):
        while True:
            m = _WRAPPED.match(c)
            if not m:
                break
            c = m.group(1)
        out.append(c)
    return out


def in_scope(path: str, scopes: Sequence[str]) -> bool:
    return any(c in scopes for c in components(path))


def _host(trace: tr.Trace, names: Sequence[str]) -> List[tr.Interval]:
    return [(e.start, e.end) for e in trace.host if e.name in names]


def _intersect(a: Sequence[tr.Interval], b: Sequence[tr.Interval]) -> List[tr.Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def decode_scope_share_pct(trace: tr.Trace, win: tr.Interval, scopes: Scopes,
                           names: Sequence[str]) -> Optional[float]:
    """Device time of the ops under any of ``names`` inside executions of
    the decode-step program, over those executions' device time, in %.
    Control-flow containers are left out (their time is their body's).
    None where no op of the decode step carries one of the program's
    scopes."""
    decode = {name for name, p in tr.programs(trace, win).items()
              if tr.runs_op(p, readers.DECODE_KERNEL)}
    total = hit = 0.0
    scoped = False
    for dev, mods in trace.modules.items():
        scope_of = scopes.get(f"/device:TPU:{dev}", {})
        ops = sorted((e for e in trace.ops[dev] if tr._inside(e, win)),
                     key=lambda e: e.start)
        starts = [e.start for e in ops]
        for mod in mods:
            if mod.name not in decode or not tr._inside(mod, win):
                continue
            total += mod.dur
            lo = bisect.bisect_left(starts, mod.start)
            hi = bisect.bisect_left(starts, mod.end)
            for e in ops[lo:hi]:
                if tr.op_name(e.name).split("_")[0] in tr.CONTAINERS:
                    continue
                path = scope_of.get(e.name, "")
                scoped = scoped or in_scope(path, SCOPES)
                if in_scope(path, names):
                    hit += e.dur
    if total <= 0 or not scoped:
        return None
    return 100.0 * hit / total


def engine_self_ms(trace: tr.Trace, win: tr.Interval) -> Optional[float]:
    """Mean host time per ``serve.step`` span less the part of it spent in
    ``serve.sync`` and ``serve.harvest`` (waiting for the device), in ms."""
    steps = [e for e in trace.host if e.name == "serve.step" and tr._inside(e, win)]
    if not steps:
        return None
    waits = tr.union(_host(trace, WAIT_SPANS), *win)
    own = [e.dur - tr.covered(_intersect(waits, [(e.start, e.end)])) for e in steps]
    return 1e-6 * sum(own) / len(own)


def idle_in_engine_share_pct(trace: tr.Trace, win: tr.Interval) -> Optional[float]:
    """Share of the traced window in which the first chip runs no program
    while the host is inside ``serve.step`` or ``serve.submit``, in %."""
    if not trace.devices or not any(e.name in ENGINE_SPANS for e in trace.host):
        return None
    busy = tr.union([(e.start, e.end) for e in trace.modules[trace.devices[0]]], *win)
    engine = tr.union(_host(trace, ENGINE_SPANS), *win)
    idle = tr.gaps(busy, *win)
    return 100.0 * tr.covered(_intersect(idle, engine)) / (win[1] - win[0])
