"""Reductions shared by the per-layer metric readers (``bench/metrics``).

Each returns None where the run holds nothing to read (no trace, no
decode step in the traced stretch), never a 0 in place of a share.
"""

from __future__ import annotations

from typing import Optional

from bench import flops, peaks
from bench import trace as tr

DECODE_KERNEL = "flash_decode_kernel"
ENGINE_PROGRAM = "jit_traced"          # the serve engine's AOT programs
EPOCH_PROGRAM = "jit_epoch_fn"         # launch.steps.make_train_epoch


def _programs(rec, pick):
    if rec.trace is None:
        return None
    progs = [p for name, p in tr.programs(rec.trace, rec.window).items() if pick(name, p)]
    return progs or None


def decode_programs(rec):
    return _programs(rec, lambda name, p: tr.runs_op(p, DECODE_KERNEL))


def prefill_programs(rec):
    return _programs(rec, lambda name, p: tr.module_name(name) == ENGINE_PROGRAM
                     and not tr.runs_op(p, DECODE_KERNEL))


def seconds_and_count(progs):
    return sum(p["seconds"] for p in progs), sum(p["count"] for p in progs)


def idle_share_pct(rec) -> Optional[float]:
    if rec.trace is None or not rec.trace.modules:
        return None
    window_s = (rec.window[1] - rec.window[0]) / 1e9
    return 100.0 * (1.0 - tr.busy_s(rec.trace, rec.window) / window_s)


def mean_span_ms(rec, name: str) -> Optional[float]:
    if rec.trace is None:
        return None
    spans = tr.host_span_seconds(rec.trace, rec.window, name)
    return 1e3 * sum(spans) / len(spans) if spans else None


def decode_step_ms(rec) -> Optional[float]:
    progs = decode_programs(rec)
    if not progs:
        return None
    sec, n = seconds_and_count(progs)
    return 1e3 * sec / n


def prefill_share_pct(rec) -> Optional[float]:
    if rec.trace is None or not rec.trace.modules:
        return None
    busy = tr.busy_s(rec.trace, rec.window) * len(rec.trace.devices)
    sec = seconds_and_count(prefill_programs(rec) or [])[0]
    return 100.0 * sec / busy if busy > 0 else None


def decode_attention_roofline_pct(rec) -> Optional[float]:
    """Least time the chip needs for the decode attention the traced steps
    did (valid K/V rows of live slots, query, output) over the kernel's
    device time."""
    if rec.trace is None or not rec.counters.get("valid_rows"):
        return None
    calls, sec = tr.op_seconds(rec.trace, rec.window, DECODE_KERNEL)
    if not calls:
        return None
    f, b = flops.decode_attention(rec.cell.conf, rec.counters["live_slot_steps"],
                                  rec.counters["valid_rows"])
    return 100.0 * peaks.bound_seconds(f, b, _kind(rec)) / sec


def decode_step_mfu_pct(rec) -> Optional[float]:
    """Least time the chip needs for the traced decode steps (every weight
    a step uses read once, the valid cache rows, the steps' matmul FLOPs)
    over the decode program's device time.  Bytes bind it."""
    progs = decode_programs(rec)
    if not progs or not rec.counters.get("decode_steps"):
        return None
    sec, n = seconds_and_count(progs)
    f, b = flops.decode_steps(rec.cell.conf, n, rec.counters)
    return 100.0 * peaks.bound_seconds(f, b, _kind(rec)) / sec


def train_mfu_pct(rec) -> Optional[float]:
    """Model FLOPs of the traced steps over the epoch program's device time
    and the chip's peak bf16 rate."""
    progs = _programs(rec, lambda name, p: tr.module_name(name) == EPOCH_PROGRAM)
    if not progs:
        return None
    sec, n = seconds_and_count(progs)
    t = rec.cell.traffic
    step = flops.train_step_flops(rec.cell.conf, t["batch"], t["seq"])
    return 100.0 * step * n * t["steps_per_dispatch"] / sec / peaks.peaks(_kind(rec))["bf16_flops"]


def _kind(rec) -> str:
    import jax

    return jax.local_devices()[0].device_kind
