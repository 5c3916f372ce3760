"""Registry exporters: JSONL events and Prometheus text.

Both read only ``Registry.snapshot()`` and ``Registry.events``:

* ``write_jsonl`` — one JSON object per line: a header record (wall-clock
  anchor + metric snapshot) followed by every event in emission order.
* ``write_prometheus`` — the text exposition format: counters, gauges,
  and histogram quantiles as ``name{quantile="0.5"}`` summary series.
* ``jax_profile`` — optional ``jax.profiler.trace`` wrapper (the
  ``--profile-dir`` flag): a no-op context when the directory is None.
  The timeline lives there: every registry span is also a profiler
  annotation, on the same clock as the device's ops.

``request_chain_rids`` is the span-chain checker the CI obs smoke asserts
with: the rids whose submit→retire lifecycle is fully covered.
"""

from __future__ import annotations

import contextlib
import json
import re
from typing import Dict, List, Set

from repro.obs.registry import Registry

# The per-request span taxonomy ContinuousEngine emits at harvest time.
REQUEST_PHASES = (
    "request/queue",      # submit -> admit (scheduler wait)
    "request/prefill",    # admit -> first token (the bucketed prefill)
    "request/decode",     # first token -> last token (decode rounds)
    "request/retire",     # last token -> harvested output
)


def write_jsonl(reg: Registry, path: str) -> None:
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "snapshot", **reg.snapshot()}) + "\n")
        for ev in reg.events:
            f.write(json.dumps(ev) + "\n")


_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    return _PROM_BAD.sub("_", name)


def prometheus_text(reg: Registry) -> str:
    snap = reg.snapshot()
    lines: List[str] = []
    for name, v in snap["counters"].items():
        n = _prom_name(name)
        lines.append(f"# TYPE {n} counter")
        lines.append(f"{n} {v}")
    for name, v in snap["gauges"].items():
        n = _prom_name(name)
        lines.append(f"# TYPE {n} gauge")
        lines.append(f"{n} {v}")
    for name, s in snap["histograms"].items():
        if not s:
            continue
        n = _prom_name(name)
        lines.append(f"# TYPE {n} summary")
        for q in (50, 90, 99):
            lines.append(f'{n}{{quantile="0.{q}"}} {s[f"p{q}"]}')
        lines.append(f"{n}_sum {s['sum']}")
        lines.append(f"{n}_count {int(s['count'])}")
    return "\n".join(lines) + "\n"


def write_prometheus(reg: Registry, path: str) -> None:
    with open(path, "w") as f:
        f.write(prometheus_text(reg))


def request_chain_rids(reg: Registry) -> Set[int]:
    """rids with a COMPLETE submit→retire chain: a parent ``request``
    span plus all four lifecycle phases pointing at it."""
    phases_by_rid: Dict[int, Set[str]] = {}
    for ev in reg.events:
        if ev.get("kind") != "span":
            continue
        rid = ev.get("attrs", {}).get("rid")
        if rid is None:
            continue
        if ev["name"] == "request" or ev["name"] in REQUEST_PHASES:
            phases_by_rid.setdefault(int(rid), set()).add(ev["name"])
    want = {"request", *REQUEST_PHASES}
    return {rid for rid, names in phases_by_rid.items() if names >= want}


@contextlib.contextmanager
def jax_profile(profile_dir=None):
    """``jax.profiler.trace`` around the block when a directory is given
    (the ``--profile-dir`` flag); identity otherwise."""
    if not profile_dir:
        yield
        return
    import jax

    with jax.profiler.trace(str(profile_dir)):
        yield
