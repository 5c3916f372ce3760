"""Find a cell's files by name, run it, and print its result line.

Everything that belongs to one cell is found by the names in
``BENCHMARK.json``: the configuration's file (``configs[].file``), the
traffic mix ``bench/traffic/<traffic>.json``, the cell's limits
``bench/limits/<workload>.json``, one reader per per-layer metric,
``bench/metrics/<metric>.py`` (a function ``read(record)`` that returns a
number, or None where it finds nothing to read), and the module of the
configuration's architecture, ``bench/arch/<architectures[0]>.py``
(``bench/model.py``).  A new cell, mix, metric or architecture is new
files and entries; no code here changes.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation as annotate  # noqa: F401  (serve, train use it)

from bench import model

TRACE_SPAN = "bench.traced"
HOST_SPANS = (TRACE_SPAN, "generator", "engine.step", "take_finished",
              "train.dispatch", "train.wait")
# Switches that would swap the chip's kernels for interpret mode or a jnp
# fallback: a measurement with them set is not of the program as served.
HIDING_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_FLASH_DECODE_IMPL")


@dataclasses.dataclass
class Cell:
    root: Path
    spec: dict
    workload: dict
    conf: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip, where the backend says."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks or [0]))


@dataclasses.dataclass
class Record:
    """What a run hands to the result line and to the per-layer readers."""

    cell: Cell
    setup_s: float
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    check: Dict[str, float] = dataclasses.field(default_factory=dict)
    # Printed beside a compared number, not compared: a resolved gap's
    # plain reading and the router flips taken, say.
    check_notes: Dict[str, dict] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    trace: object = None              # bench.trace.Trace of the traced stretch
    window: Optional[tuple] = None    # (start_ns, end_ns) on the trace's clock


class _Untraced:
    counters: dict = {}
    trace = window = None

    def poll(self, elapsed: float) -> None:
        pass

    def close(self) -> None:
        pass

    def load(self) -> None:
        pass


class _Traced:
    """A profiler trace from the first poll at or after ``at`` of the
    window, for ``dur`` seconds counted from that start (a tick that runs
    past ``at`` moves the trace, it does not shorten it), or to the
    window's end; bracketed by program counters read (with a device sync)
    at both ends."""

    def __init__(self, cell: Cell, read_counters: Callable[[], dict], at: float, dur: float):
        self.cell, self.read = cell, read_counters
        self.t_on, self.dur, self.t_off = at, dur, math.inf
        self.state = "before"
        self.counters: dict = {}
        self.trace = self.window = None
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._span = None
        self._start: dict = {}

    def poll(self, elapsed: float) -> None:
        if self.state == "before" and elapsed >= self.t_on:
            self._start = self.read()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = annotate(TRACE_SPAN)
            self._span.__enter__()
            self.t_off = elapsed + self.dur
            self.state = "on"
        elif self.state == "on" and elapsed >= self.t_off:
            self.close()

    def close(self) -> None:
        if self.state != "on":
            return
        end = self.read()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.counters = {k: end[k] - self._start[k] for k in end}
        self.state = "done"

    def load(self) -> None:
        from bench import trace as trace_lib

        if self.state != "done":
            return
        path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        self.trace = trace_lib.load(sorted(path)[-1], HOST_SPANS)
        self.window = trace_lib.window(self.trace, TRACE_SPAN)
        shutil.rmtree(self.dir, ignore_errors=True)


def profile_window(cell: Cell, read_counters: Callable[[], dict], at: float, dur: float):
    """The traced stretch of a ``--trace 1`` run (nothing otherwise):
    from ``at`` of the window, for ``dur`` seconds from its start (or to
    the window's end)."""
    if not cell.trace:
        return _Untraced()
    return _Traced(cell, read_counters, at * cell.seconds, min(dur, 0.6 * cell.seconds))


# ---------------------------------------------------------------------------
# Lookup by name
# ---------------------------------------------------------------------------

def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: Path, name: str):
    spec = _load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    workload = wl[name]
    config = {c["name"]: c for c in spec["configs"]}[workload["config"]]
    try:
        conf = model.read_conf(root, config["file"])
    except FileNotFoundError as e:
        raise SystemExit(f"workload {name!r}: {e}") from None
    traffic = _load_json(root / "bench" / "traffic" / f"{workload['traffic']}.json")
    limits = _load_json(root / "bench" / "limits" / f"{name}.json")
    return spec, workload, conf, traffic, limits


def cell_metrics(spec: dict, workload: str, trace: bool) -> List[dict]:
    """The end-to-end metrics a cell reports, or with ``trace`` its
    per-layer ones (listed for it, or reported wherever the end-to-end
    metric they move is)."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if listed(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]


def read_metric(root: Path, name: str, rec: Record) -> Optional[float]:
    return model.load_module(root / "bench" / "metrics" / f"{name}.py", "bench_metric_").read(rec)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def result_line(rec: Record, metrics: List[dict]) -> dict:
    cell = rec.cell
    out_metrics = {}
    for m in metrics:
        if m["name"] == "setup_s":
            v = rec.setup_s
        elif cell.trace:
            v = read_metric(cell.root, m["name"], rec)
        else:
            v = rec.e2e.get(m["name"])
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    devs = jax.local_devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": rec.memory_peak_bytes}
    line = {"correct": False, "attempted": rec.attempted, "failed": rec.failed,
            "metrics": out_metrics, "device": device}
    if cell.trace and rec.trace is not None:
        from bench import trace as trace_lib

        device["busy_s"] = trace_lib.busy_s(rec.trace, rec.window)
        device["window_s"] = (rec.window[1] - rec.window[0]) / 1e9
        line["breakdown"] = {
            "device_ops": trace_lib.top_ops(rec.trace, rec.window),
            "idle_gaps": trace_lib.idle_gaps(rec.trace, rec.window),
        }
    check = {k: {"value": _finite(rec.check.get(k)), "limit": lim["limit"],
                 **rec.check_notes.get(k, {})}
             for k, lim in cell.limits.items()}
    line["correct"] = bool(
        rec.attempted > 0 and rec.failed == 0 and check
        and all(c["value"] is not None and c["value"] <= c["limit"] for c in check.values()))
    line["check"] = check
    return line


def main(argv=None, *, t_process: float, root: Optional[Path] = None,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root) if root else Path(__file__).resolve().parents[1]
    spec, workload, conf, traffic, limits = find_cell(root, args.workload)

    devices = jax.devices()
    if require_tpu:
        hidden = [v for v in HIDING_ENV if os.environ.get(v)]
        if hidden:
            print(f"bench: unset {hidden}: they hide the kernels", file=sys.stderr)
            return 3
        if devices[0].platform != "tpu":
            print(f"bench: needs a TPU; JAX found {devices[0].platform}", file=sys.stderr)
            return 3
        from repro.launch.compile_cache import setup_compile_cache

        setup_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if len(devices) < workload["chips"]:
        print(f"bench: cell needs {workload['chips']} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 3

    cell = Cell(root=root, spec=spec, workload=workload, conf=conf, traffic=traffic,
                limits=limits, seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace), t_process=t_process)
    if traffic["kind"] == "serve":
        from bench import serve as kind
    elif traffic["kind"] == "train":
        from bench import train as kind
    else:
        raise SystemExit(f"unknown traffic kind {traffic['kind']!r}")
    rec = kind.run(cell)
    line = result_line(rec, cell_metrics(spec, workload["name"], cell.trace))
    for k, c in line["check"].items():
        notes = "".join(f" {n} {v!r}" for n, v in c.items() if n not in ("value", "limit"))
        print(f"check {k} {c['value']!r} limit {c['limit']!r}{notes}", file=sys.stderr,
              flush=True)
    print(json.dumps(line), flush=True)
    return 0
