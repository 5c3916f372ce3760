"""Serving cells: drive ``ContinuousEngine`` (the contiguous bf16 slot pool
that ``launch.serve.generate`` picks) through ``submit`` / ``step`` /
``take_finished`` under a seeded schedule, then hold a sample of what it
served to the plain reference.
"""

from __future__ import annotations

import gc
import heapq
import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import loadgen, model, reference
from bench.harness import Record, annotate, profile_window

DRAIN_S = 60.0           # a request may finish this long after the window
TRACE_AT, TRACE_S = 0.3, 3.0   # traced stretch: from 30% of the window, 3 s


def _request_keys(seed: int, n: int) -> np.ndarray:
    base = jax.random.fold_in(model.seed_key(seed), 0xC0DE)
    return np.asarray(jax.jit(jax.vmap(lambda i: jax.random.fold_in(base, i)))(
        jnp.arange(n, dtype=jnp.uint32)))


def engine_for(cfg, pool_conf: dict):
    from repro.serve import ContinuousEngine, PoolConfig

    return ContinuousEngine(cfg, PoolConfig(
        max_slots=pool_conf["max_slots"], max_new=pool_conf["max_new"],
        max_prompt=pool_conf["max_prompt"]))


def warm(engine, params, reqs) -> None:
    """Run every program this traffic uses once: one request per prefill
    bucket its prompts fall in, and the decode step."""
    buckets = sorted({engine.bucket_for(len(r.prompt)) for r in reqs})
    for i, b in enumerate(buckets):
        length = min(b, engine.pool.max_prompt)
        engine.submit(np.zeros((length,), np.int32), 2, key=jax.random.PRNGKey(i))
    engine.run(params)
    engine.device_counters()


class Window:
    """The measured stretch: submissions on schedule, engine steps, and the
    host time at which each request's tokens reached the host."""

    def __init__(self, engine, params, reqs: List[loadgen.Req], traffic: dict,
                 keys: np.ndarray):
        self.engine, self.params, self.reqs, self.keys = engine, params, reqs, keys
        self.closed = traffic["arrivals"]["process"] == "closed"
        self.per_client = traffic["arrivals"].get("requests_per_client", 0)
        self.pending: list = []            # heap of (due_at, rid)
        self.inflight: Dict[int, loadgen.Req] = {}

    def start(self, t0: float) -> None:
        for r in self.reqs:
            if r.due is not None:
                r.due_at = t0 + r.due
                heapq.heappush(self.pending, (r.due_at, r.rid))

    def submit_due(self, now: float, limit: float) -> None:
        eng = self.engine
        while self.pending and self.pending[0][0] <= now and self.pending[0][0] < limit:
            _, rid = heapq.heappop(self.pending)
            r = self.reqs[rid]
            r.submitted = time.perf_counter()
            r.handle = eng.submit(r.prompt, r.n_out, key=self.keys[rid])
            self.inflight[r.handle.rid] = r

    def step(self) -> None:
        with annotate("engine.step"):
            self.engine.step(self.params)
        with annotate("take_finished"):
            done = self.engine.take_finished()
        now = time.perf_counter()
        for h in done:
            r = self.inflight.pop(h.rid)
            r.finished, r.tokens = now, h.tokens
            if self.closed and (r.rid + 1) % self.per_client:
                nxt = self.reqs[r.rid + 1]
                nxt.due_at = now
                heapq.heappush(self.pending, (now, nxt.rid))

    def run(self, seconds: float, traced) -> tuple:
        t0 = time.perf_counter()
        end = t0 + seconds
        self.start(t0)
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            traced.poll(now - t0)
            with annotate("generator"):
                self.submit_due(now, end)
            if self.inflight:
                self.step()
            else:
                nxt = self.pending[0][0] if self.pending else end
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        traced.close()
        deadline = end + DRAIN_S
        while time.perf_counter() < deadline:
            self.submit_due(time.perf_counter(), end)
            if self.inflight:
                self.step()
            elif not (self.pending and self.pending[0][0] < end):
                break
        return t0, end


def _chains(prefill_keys: np.ndarray, rounds: int) -> tuple:
    """The link keys of each request: the prefill's, and one per decode
    round, as the request's key chain gives them."""
    def one(k):
        k, sub = jax.random.split(k)

        def body(k, _):
            k, s = jax.random.split(k)
            return k, s
        return sub, jax.lax.scan(body, k, None, length=rounds)[1]
    pre, rnd = jax.jit(jax.vmap(one))(jnp.asarray(prefill_keys))
    return np.asarray(pre), np.asarray(rnd)


def pick_sample(done: List[loadgen.Req], seed: int, check: dict) -> List[loadgen.Req]:
    """Requests to hold to the reference, drawn from the seed: the one with
    the most served tokens, then others until ``min_tokens`` are in."""
    if not done:
        return []
    rng = np.random.default_rng(seed ^ 0xC4EC)
    longest = max(done, key=lambda r: (r.n_out, -r.rid))
    out, tokens = [longest], longest.n_out
    for i in rng.permutation(len(done)):
        r = done[int(i)]
        if len(out) >= check["max_requests"] or tokens >= check["min_tokens"]:
            break
        if r is not longest:
            out.append(r)
            tokens += r.n_out
    return out


def check_sample(conf: dict, traffic: dict, seed: int, sample, keys, control=False,
                 gap=None) -> dict:
    """Widest gap of a served token below the reference's best logit over
    the sample (and the control's, with ``control``).  ``gap`` is the
    cell's ``served_gap`` limits entry; with ``router_tie`` in it, router
    near-ties are resolved (``reference.serve_gaps``)."""
    link = traffic["link"]
    ge = reference.ge_params(link["loss_rate"], **link.get("channel_params", {}))
    pool = traffic["pool"]
    rows = np.zeros((traffic["check"]["max_requests"], 2), np.uint32)
    rows[: len(sample)] = keys[[r.rid for r in sample]]
    pre, rnd = _chains(rows, pool["max_new"])
    items = [{"prompt": r.prompt, "tokens": np.asarray(r.tokens), "prefill_key": pre[j],
              "round_keys": rnd[j][: len(r.tokens) - 1]} for j, r in enumerate(sample)]
    shape = (len(rows), pool["max_prompt"] + pool["max_new"])
    return reference.serve_gaps(conf, seed, items, ge[-1], ge, shape, with_control=control,
                                gap=gap)


def _counters(engine) -> dict:
    """Program counters at one instant (one device sync): every running
    total the program keeps on the device (``COUNTER_KEYS``; an
    architecture's counts in ``bench/arch`` may read any of them), with
    valid cache rows summed over live slots as ``valid_rows``; and the
    engine's host-side step and live-slot counts.  Only totals: the traced
    window takes the difference of its two readings."""
    from repro.obs.device import COUNTER_KEYS

    dev = engine.device_counters()
    out = {k: dev[k] for k in COUNTER_KEYS}
    out["valid_rows"] = out.pop("valid_tokens")
    return dict(out, engine_steps=engine.steps, live_slot_steps=engine.busy_slot_steps)


def run(cell) -> Record:
    conf, traffic = cell.conf, cell.traffic
    cfg = model.program_config(conf, traffic["link"])
    params = model.program_params(conf, cell.seed)
    model.check_layout(params, cfg)
    engine = engine_for(cfg, traffic["pool"])
    reqs = loadgen.schedule(traffic, cell.seed, cell.seconds, conf["vocab_size"])
    keys = _request_keys(cell.seed, len(reqs))
    warm(engine, params, reqs)
    jax.block_until_ready(params)

    win = Window(engine, params, reqs, traffic, keys)
    traced = profile_window(cell, lambda: _counters(engine), TRACE_AT, TRACE_S)
    setup_s = time.perf_counter() - cell.t_process
    steps0, busy0 = engine.steps, engine.busy_slot_steps
    from repro.analysis.guards import no_recompile
    with no_recompile(engines=(engine,)):
        t0, end = win.run(cell.seconds, traced)
    traced.load()
    rec = Record(cell=cell, setup_s=setup_s)
    rec.counters.update(traced.counters)
    rec.counters["slot_occupancy"] = (engine.busy_slot_steps - busy0) / max(
        1, (engine.steps - steps0) * engine.pool.max_slots)
    rec.trace, rec.window = traced.trace, traced.window

    due = [r for r in reqs if t0 <= r.due_at < end]
    done = [r for r in due if not math.isnan(r.finished)]
    rec.attempted, rec.failed = len(due), len(due) - len(done)
    lat = np.array([r.finished - r.due_at for r in done])
    if len(lat):
        in_window = sum(r.n_out for r in reqs if t0 <= r.finished <= end)
        rec.e2e.update({
            "output_tokens_per_s": in_window / (end - t0),
            "request_latency_p95_ms": float(np.percentile(lat, 95) * 1e3),
        })
    # Before the profiler starts: its stop can stall the host for seconds.
    late = loadgen.lateness(reqs, t0, t0 + TRACE_AT * cell.seconds)
    if len(late):
        rec.counters["gen_late_p95_ms"] = float(np.percentile(late, 95) * 1e3)
    rec.memory_peak_bytes = cell.memory_peak()

    sample = pick_sample(done, cell.seed, traffic["check"])
    del engine, params, win, traced
    gc.collect()
    if sample:
        gap = cell.limits.get("served_gap")
        got = check_sample(conf, traffic, cell.seed, sample, keys, gap=gap)
        rec.check["served_gap"] = got["served"]
        if "router_flips" in got:
            rec.check_notes["served_gap"] = {"plain": got["served_plain"],
                                             "router_flips": got["router_flips"]}
    else:
        rec.check["served_gap"] = math.inf
    return rec
