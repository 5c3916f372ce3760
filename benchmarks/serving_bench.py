"""Serving benchmark: continuous-batching slot pool vs whole-generation engine.

Builds a mixed-length Poisson workload (``--clients`` Poisson processes,
prompt lengths spread over >= 3 power-of-two buckets), replays it in
arrival order through

* the **continuous engine** (``repro.serve.continuous``): slot-pooled,
  bucketed prefill, one fused decode step — after the per-bucket warm-up
  the whole run executes with ZERO new XLA builds (AOT ``Compiled``
  programs cannot retrace; ``engine.compiles`` proves it), and
* the **whole-generation engine** (``repro.serve.DecodeEngine``) serving
  each request at its exact (prompt_len, num_tokens) signature, batch 1 —
  the recompile-storm baseline: one AOT build per distinct signature,
  then sequential per-request execution.

Emits ``BENCH_serving.json`` with sustained tokens/s, request completion
p50/p99 under saturated replay, total/steady-state compile counts, slot
occupancy, and the old-engine baseline (warm and cold).

    PYTHONPATH=src python -m benchmarks.serving_bench [--smoke] \
        [--out BENCH_serving.json] [--assert-max-compiles N] \
        [--assert-zero-steady-compiles] [--assert-min-rps 1.0] \
        [--assert-min-speedup 2.0]

``--paged`` switches to the density comparison instead: a contiguous slot
pool vs a PAGED block pool holding no more cache HBM, replaying one
saturated workload through both.  The contiguous engine can only hold as
many requests as worst-case ``max_seq`` slots fit; the paged engine
reserves per-request blocks, so the same bytes sustain several times the
in-flight requests (``active_median`` per decode step) and admission
writes scale with the prompt's bucket instead of ``max_seq``.  Emits
``BENCH_serving_paged.json``; greedy outputs are cross-checked
token-for-token between the two engines, and both keep
``compiles == num_buckets + 1``.

    PYTHONPATH=src python -m benchmarks.serving_bench --paged \
        [--assert-min-sustained-ratio 2.0] [--out BENCH_serving_paged.json]

``--sla`` is the SLA/chaos headline: a mixed-class Poisson workload
(interactive / standard / batch priorities with per-class deadlines) in
**virtual time** (a ``VirtualClock`` advanced a fixed ``dt`` per engine
step, so deadline hit-rates are deterministic and CI-gateable), with a
mid-run ``channel_collapse`` killing uplinks and a ``block_pool_squeeze``
starving the paged pool — run twice through the SAME engine shape, once
FIFO (no scheduler) and once under ``SLAScheduler`` (EDF-within-priority,
preemption, expiry, bounded retry).  Emits ``BENCH_serving_sla.json``
with per-class p50/p99 and deadline-hit-rate for both arms; the CI gate
asserts every submitted request resolves terminally and the scheduled
high-priority hit-rate beats the unscheduled one.

    PYTHONPATH=src python -m benchmarks.serving_bench --sla \
        [--assert-all-terminal] [--assert-min-hi-hit-rate 0.6] \
        [--assert-scheduled-beats-unscheduled] [--out BENCH_serving_sla.json]

``--sharded-serve`` is the mesh-scaling comparison: the same saturated
mixed-length replay through ONE slot pool vs the sharded router
(``repro.serve.router``) with an identically sized pool per device —
token outputs are cross-checked identical between the arms, every shard
must hold ``compiles == num_buckets + 1``, and a second phase runs the
mixed-SLA virtual-time workload through the router-fronted scheduler.
Emits ``BENCH_serving_sharded.json``; the aggregate-throughput gate
needs real parallel devices (CI forces 4 with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python -m benchmarks.serving_bench --sharded-serve \
        [--num-shards N] [--assert-min-sharded-speedup 1.8] \
        [--out BENCH_serving_sharded.json]
"""

from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis.guards import no_recompile
from repro.configs import ARCHITECTURES, get_config
from repro.core import link as link_lib
from repro.launch.compile_cache import setup_compile_cache
from repro.models import cache as cache_lib, lm
from repro.net.chaos import (
    ChaosSchedule,
    EngineChaos,
    _OverrideChannel,
    block_pool_squeeze,
    channel_collapse,
)
from repro.net.channels import make_channel
from repro.net.protocol import make_protocol
from repro.obs import exporters
from repro.obs.stats import latency_summary
from repro.serve import (
    SLA,
    ContinuousEngine,
    DecodeEngine,
    PoolConfig,
    PoolExhausted,
    ShardedEngine,
    SLAScheduler,
    VirtualClock,
)

logger = obs.get_logger("serving_bench")


def build_workload(
    n_clients: int,
    rate_hz: float,
    duration_s: float,
    lengths,
    vocab: int,
    seed: int = 0,
    min_requests: int = 8,
):
    """Poisson arrivals per client, merged and sorted; each request gets a
    prompt whose length cycles through ``lengths`` (>= 3 buckets)."""
    rng = np.random.RandomState(seed)
    arrivals = []
    for c in range(n_clients):
        t = rng.exponential(1.0 / rate_hz)
        while t < duration_s:
            arrivals.append((t, c))
            t += rng.exponential(1.0 / rate_hz)
    arrivals.sort()
    while len(arrivals) < min_requests:          # tiny-duration safety net
        arrivals.append((duration_s, len(arrivals) % n_clients))
    prompts = []
    for i, (t, c) in enumerate(arrivals):
        L = int(lengths[i % len(lengths)])
        prompts.append(rng.randint(0, vocab, size=(L,)).astype(np.int32))
    return arrivals, prompts


def run_bench(
    arch: str = "qwen1.5-0.5b",
    n_clients: int = 24,
    rate_hz: float = 1.0,
    duration_s: float = 1.0,
    lengths=(5, 7, 11, 14, 22, 28),
    tokens: int = 16,
    max_slots: int = 8,
    loss_rate: float = 0.1,
    channel: str = "iid",
    seed: int = 0,
    full_size: bool = False,
) -> dict:
    import dataclasses

    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=loss_rate, channel=channel)
    )
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    arrivals, prompts = build_workload(
        n_clients, rate_hz, duration_s, lengths, cfg.vocab_size, seed=seed
    )
    n_req = len(prompts)
    base_key = jax.random.PRNGKey(seed)

    # ---- continuous engine -------------------------------------------------
    pool = PoolConfig(
        max_slots=max_slots,
        max_new=max(16, tokens),
        max_prompt=max(int(max(lengths)), 8),
    )
    eng = ContinuousEngine(cfg, pool)
    buckets = sorted({eng.bucket_for(len(p)) for p in prompts})

    # Warm-up: one throwaway request per bucket compiles every program the
    # workload can touch (num_buckets prefills + 1 decode step).
    for i, b in enumerate(buckets):
        p = next(p for p in prompts if eng.bucket_for(len(p)) == b)
        eng.submit(p, 1, key=jax.random.fold_in(base_key, 10_000 + i))
    eng.run(params)
    warm_compiles = eng.compiles
    warm_compile_s = eng.compile_s

    t0 = time.perf_counter()
    # The steady-state contract, enforced at runtime: the warmed replay
    # performs zero new XLA builds (guard watches jax.monitoring AND
    # eng.compiles; a violation raises instead of silently skewing stats).
    with no_recompile(engines=(eng,)):
        reqs = [
            eng.submit(p, tokens, key=jax.random.fold_in(base_key, i))
            for i, p in enumerate(prompts)
        ]
        eng.run(params)
    t_eng = time.perf_counter() - t0
    completion = [r.t_done - t0 for r in reqs]
    eng_stats = {
        "tokens_per_s": n_req * tokens / t_eng,
        "requests_per_s": n_req / t_eng,
        "wall_s": t_eng,
        "compiles_total": eng.compiles,
        "compiles_warmup": warm_compiles,
        "compiles_steady": eng.compiles - warm_compiles,
        "compile_s": eng.compile_s,
        "num_buckets": eng.num_buckets,
        "traces": eng.traces,
        "slot_occupancy": eng.stats()["slot_occupancy"],
        "max_slots": max_slots,
        **latency_summary(completion),
        "device": eng.device_counters(),
        **{f"request_{k}": v for k, v in eng.request_stats().items()},
    }
    eng.publish_device_counters()

    # ---- whole-generation baseline ----------------------------------------
    # Each request served at its exact signature, batch 1 — under the mixed
    # workload that is one AOT build per distinct (prompt_len, tokens).
    old = DecodeEngine()
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):          # cold pass: the recompile storm
        old.generate(params, cfg, jnp.asarray(p)[None], tokens,
                     key=jax.random.fold_in(base_key, i))
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    done_at = []
    for i, p in enumerate(prompts):          # warm pass: steady-state
        old.generate(params, cfg, jnp.asarray(p)[None], tokens,
                     key=jax.random.fold_in(base_key, i))
        done_at.append(time.perf_counter() - t0)
    t_warm = time.perf_counter() - t0
    ref_stats = {
        "tokens_per_s": n_req * tokens / t_warm,
        "tokens_per_s_cold": n_req * tokens / t_cold,
        "wall_s": t_warm,
        "wall_s_cold": t_cold,
        "signatures_compiled": old.num_compiled,
        "compile_s": sum(e.compile_s for e in old._compiled.values()),
        **latency_summary(done_at),
    }

    return {
        "bench": "serving",
        "arch": arch,
        "n_clients": n_clients,
        "rate_hz": rate_hz,
        "n_requests": n_req,
        "tokens": tokens,
        "prompt_lengths": sorted(set(int(len(p)) for p in prompts)),
        "buckets": [int(b) for b in buckets],
        "loss_rate": loss_rate,
        "channel": channel,
        "backend": jax.default_backend(),
        "engine": eng_stats,
        "whole_generation": ref_stats,
        "speedup": eng_stats["tokens_per_s"] / max(ref_stats["tokens_per_s"], 1e-9),
        "speedup_vs_cold": eng_stats["tokens_per_s"]
        / max(ref_stats["tokens_per_s_cold"], 1e-9),
    }


def _replay(eng, params, prompts, tokens, base_key):
    """Warm the engine's programs on one throwaway request per bucket,
    then replay the saturated workload under the no-recompile guard.
    Returns (requests, wall_s) with the concurrency window reset so
    ``active_median`` measures the replay only."""
    buckets = sorted({eng.bucket_for(len(p)) for p in prompts})
    for i, b in enumerate(buckets):
        p = next(p for p in prompts if eng.bucket_for(len(p)) == b)
        eng.submit(p, 1, key=jax.random.fold_in(base_key, 10_000 + i))
    eng.run(params)
    eng.active_per_step.clear()
    t0 = time.perf_counter()
    with no_recompile(engines=(eng,)):
        reqs = [
            eng.submit(p, tokens, key=jax.random.fold_in(base_key, i))
            for i, p in enumerate(prompts)
        ]
        eng.run(params)
    return reqs, time.perf_counter() - t0


def run_paged_bench(
    arch: str = "qwen1.5-0.5b",
    n_requests: int = 24,
    tokens: int = 8,
    loss_rate: float = 0.1,
    channel: str = "iid",
    seed: int = 0,
    full_size: bool = False,
) -> dict:
    """Contiguous slot pool vs paged block pool at equal (or less) cache
    HBM, one saturated replay each.  The contiguous pool's HBM budget
    (``max_slots`` worst-case ``max_seq`` caches) is converted into pool
    blocks; short requests then reserve only their own blocks, so the
    paged engine keeps several times the requests in flight per step."""
    import dataclasses

    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=loss_rate, channel=channel)
    )
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    base_key = jax.random.PRNGKey(seed)

    # Contiguous baseline: 2 worst-case slots.
    pool_c = PoolConfig(max_slots=2, max_new=32, max_prompt=24)
    contig_hbm = cache_lib.cache_bytes(cfg, pool_c.max_slots, pool_c.max_seq)
    # Paged pool holding AT MOST the same bytes: block_pool_bytes is
    # linear in num_blocks with zero intercept, so size by the per-block
    # cost (block 0, the trash block, pays for itself out of the budget).
    block_size = 8
    per_block = cache_lib.block_pool_bytes(cfg, 3, block_size) \
        - cache_lib.block_pool_bytes(cfg, 2, block_size)
    num_blocks = contig_hbm // per_block
    pool_p = PoolConfig(
        max_slots=8, max_new=32, max_prompt=24,
        paged=True, block_size=block_size, num_blocks=int(num_blocks),
    )
    paged_hbm = cache_lib.block_pool_bytes(cfg, pool_p.total_blocks, block_size)
    assert paged_hbm <= contig_hbm, (paged_hbm, contig_hbm)

    # Saturated workload: everything submitted up front.  Short prompts
    # (one power-of-two bucket) keep the reservation arithmetic visible —
    # each request needs ceil(max(8, len+tokens) / 8) blocks vs a whole
    # contiguous max_seq slot.
    rng = np.random.RandomState(seed)
    prompts = [
        rng.randint(0, cfg.vocab_size, size=(int(3 + i % 4),)).astype(np.int32)
        for i in range(n_requests)
    ]

    results = {}
    engines = {}
    for name, pool in (("contiguous", pool_c), ("paged", pool_p)):
        eng = ContinuousEngine(cfg, pool)
        reqs, wall = _replay(eng, params, prompts, tokens, base_key)
        s = eng.stats()
        results[name] = {
            "wall_s": wall,
            "tokens_per_s": n_requests * tokens / wall,
            "max_slots": pool.max_slots,
            "cache_hbm_bytes": contig_hbm if name == "contiguous" else paged_hbm,
            "sustained_in_flight": s["active_median"],
            "active_peak": s["active_peak"],
            "active_mean": s["active_mean"],
            "compiles": eng.compiles,
            "num_buckets": eng.num_buckets,
            **{k: s[k] for k in
               ("pool_blocks_total", "peak_blocks_used", "blocks_written")
               if k in s},
        }
        engines[name] = (eng, reqs)
        assert eng.compiles == eng.num_buckets + 1, (
            name, eng.compiles, eng.num_buckets
        )

    # Same request keys through both engines -> identical greedy tokens
    # (each engine is separately pinned to generate_reference in tests;
    # the cross-check here keeps the bench honest end-to-end).
    for rc, rp in zip(engines["contiguous"][1], engines["paged"][1]):
        np.testing.assert_array_equal(rc.tokens, rp.tokens)

    # Admission-copy bytes: the paged write scales with the bucket, the
    # contiguous write is a constant full slot.
    admission = {
        "contiguous_any_bucket": cache_lib.admission_write_bytes(
            cfg, pool_c.max_seq, pool_c.max_bucket
        ),
        "paged_bucket_8": cache_lib.admission_write_bytes(
            cfg, pool_p.max_seq, 8, paged=True, block_size=block_size
        ),
        "paged_bucket_16": cache_lib.admission_write_bytes(
            cfg, pool_p.max_seq, 16, paged=True, block_size=block_size
        ),
        "paged_bucket_32": cache_lib.admission_write_bytes(
            cfg, pool_p.max_seq, 32, paged=True, block_size=block_size
        ),
    }
    assert admission["contiguous_any_bucket"] == cache_lib.cache_bytes(
        cfg, 1, pool_c.max_seq
    )
    assert (admission["paged_bucket_8"] < admission["paged_bucket_16"]
            < admission["paged_bucket_32"]
            <= admission["contiguous_any_bucket"])

    ratio = results["paged"]["sustained_in_flight"] / max(
        results["contiguous"]["sustained_in_flight"], 1e-9
    )
    return {
        "bench": "serving_paged",
        "arch": arch,
        "n_requests": n_requests,
        "tokens": tokens,
        "block_size": block_size,
        "loss_rate": loss_rate,
        "channel": channel,
        "backend": jax.default_backend(),
        "equal_hbm_bytes": {"contiguous": contig_hbm, "paged": paged_hbm},
        "admission_write_bytes": admission,
        "contiguous": results["contiguous"],
        "paged": results["paged"],
        "sustained_ratio": ratio,
    }


# ---------------------------------------------------------------------------
# --sla mode: mixed-SLA chaos workload, scheduled vs FIFO, in virtual time
# ---------------------------------------------------------------------------

# Class mix cycles i % 3 → interactive / standard / batch.  Deadlines are
# VIRTUAL seconds (the driver advances the clock dt_step per engine step,
# so "one decode step" is the time unit scaled by dt_step — deterministic
# on any machine) expressed as multiples of the nominal unqueued service
# time ((tokens + 1 steps) * dt_step): 2x for interactive (meetable only
# with immediate admission), 5x for standard, best-effort for batch.
_SLA_CLASS_NAMES = ("interactive", "standard", "batch")


def sla_classes(tokens: int, dt_step: float):
    service_s = (tokens + 1) * dt_step
    return (
        ("interactive", 2, 2.0 * service_s),
        ("standard", 1, 5.0 * service_s),
        ("batch", 0, math.inf),
    )


def build_sla_workload(
    n_requests: int,
    span_s: float,
    chaos: ChaosSchedule,
    vocab: int,
    classes,
    seed: int = 0,
    n_packets: int = 12,
):
    """Poisson arrivals in virtual time, each crossing a lossy ARQ uplink
    BEFORE reaching the engine.  A ``channel_collapse`` window overrides
    the uplink loss (the real channel's burst state is not advanced —
    same semantics as ``net.simulator``): requests arriving inside a
    total collapse exhaust the ARQ budget and are dropped at the uplink,
    never submitted.  Returns per-request dicts shared by both arms."""
    rng = np.random.RandomState(seed)
    rate = n_requests / span_s
    t, arrivals = 0.0, []
    while len(arrivals) < n_requests:
        t += rng.exponential(1.0 / rate)
        arrivals.append(t)
    protocol = make_protocol("arq", max_rounds=4)
    channel = make_channel("ge", loss_rate=0.1)
    ch_state = channel.init_state(rng)
    slot_t = link_lib.ChannelConfig().slot_time_s()
    items = []
    for i, t in enumerate(arrivals):
        name, pri, deadline = classes[i % len(classes)]
        override = chaos.loss_override(t)
        if override is None:
            result, ch_state = protocol.run_round(
                rng, channel, ch_state, n_packets
            )
        else:
            result, _ = protocol.run_round(
                rng, _OverrideChannel(override), None, n_packets
            )
        length = int(4 + i % 4)          # one power-of-two bucket (8)
        items.append({
            "idx": i,
            "cls": name,
            "sla": SLA(deadline_s=deadline, priority=pri, class_name=name),
            "deadline_s": deadline,
            "prompt": rng.randint(0, vocab, size=(length,)).astype(np.int32),
            "vt": t + result.slots * slot_t,       # uplink latency shifts it
            "dropped": result.delivered_fraction < 0.2,
        })
    return items


def _drive_sla_arm(
    cfg, params, pool: PoolConfig, items, chaos: ChaosSchedule,
    tokens: int, dt_step: float, base_key, scheduled: bool,
    make_engine=None,
):
    """One virtual-time replay: submit arrivals as the clock passes them,
    one engine step + one ``dt_step`` advance per iteration, chaos applied
    at each step's virtual now.  Returns (per-item bookkeeping, engine,
    scheduler).  ``make_engine`` swaps the engine under the same driver —
    the sharded mode passes a ``ShardedEngine`` factory so the identical
    workload runs through the router-fronted scheduler."""
    items = [dict(it) for it in sorted(items, key=lambda it: it["vt"])]
    eng = make_engine() if make_engine else ContinuousEngine(cfg, pool)
    clock = VirtualClock()
    sched = None
    if scheduled:
        sched = SLAScheduler(
            clock=clock, backoff_s=dt_step, backoff_cap_s=4 * dt_step,
            max_retries=256,
        )
        eng.attach_scheduler(sched)
    # Warm every bucket + the decode step before the guarded replay.  The
    # router warms EVERY shard through its admit-and-preempt warm();
    # the single engine warms through one throwaway request per bucket
    # (trivially admissible regardless of pool size).
    if hasattr(eng, "warm"):
        eng.warm(params, [len(it["prompt"]) for it in items])
    else:
        for i, b in enumerate(sorted(
                {eng.bucket_for(len(it["prompt"])) for it in items})):
            p = next(it["prompt"] for it in items
                     if eng.bucket_for(len(it["prompt"])) == b)
            eng.submit(p, 1, key=jax.random.fold_in(base_key, 50_000 + i))
            eng.run(params)
    echaos = EngineChaos(eng, chaos)
    i = 0
    exhausted = 0
    submitted = []
    with no_recompile(engines=(eng, *getattr(eng, "shards", ()))):
        for _ in range(200_000):
            now = clock.now
            echaos.apply(now)
            while i < len(items) and items[i]["vt"] <= now:
                it = items[i]
                i += 1
                if it["dropped"]:
                    continue
                it["req"] = eng.submit(
                    it["prompt"], tokens,
                    key=jax.random.fold_in(base_key, it["idx"]),
                    sla=it["sla"] if scheduled else None,
                )
                submitted.append(it)
            try:
                eng.step(params)
            except PoolExhausted:
                # Unscheduled backpressure: nothing to shed here — the
                # squeeze window eventually closes; count and carry on.
                exhausted += 1
            clock.advance(dt_step)
            for it in submitted:
                if "vt_done" not in it and it["req"].terminal:
                    it["vt_done"] = clock.now
            idle = not eng.active and not eng._queue and not (
                sched is not None and sched.pending
            )
            if idle and i >= len(items):
                break
            if idle and items[i]["vt"] > clock.now:
                clock.now = items[i]["vt"]       # idle skip-ahead
        else:
            raise RuntimeError("sla bench driver did not drain")
    eng.harvest()
    return items, eng, sched, exhausted


def _sla_class_summary(items, tokens_deadline_from="vt"):
    """Per-class served/completed/hit accounting from the driver's own
    virtual-time bookkeeping (identical metric for both arms)."""
    out = {}
    for name in _SLA_CLASS_NAMES:
        rows = [it for it in items if it["cls"] == name]
        served = [it for it in rows if not it["dropped"]]
        completed = [
            it for it in served if it.get("req") is not None
            and it["req"].state == "completed"
        ]
        hits = [
            it for it in completed
            if it["vt_done"] <= it["vt"] + it["deadline_s"]
        ]
        lat = sorted(it["vt_done"] - it["vt"] for it in completed)
        out[name] = {
            "submitted": len(rows),
            "uplink_dropped": sum(it["dropped"] for it in rows),
            "served": len(served),
            "completed": len(completed),
            "expired": sum(
                it.get("req") is not None and it["req"].state == "expired"
                for it in served
            ),
            "rejected": sum(
                it.get("req") is not None and it["req"].state == "rejected"
                for it in served
            ),
            "deadline_hit_rate": len(hits) / len(served) if served else 1.0,
            "latency_p50_vs": lat[len(lat) // 2] if lat else None,
            "latency_p99_vs": lat[min(len(lat) - 1,
                                      int(0.99 * len(lat)))] if lat else None,
        }
    return out


def run_sla_bench(
    arch: str = "qwen1.5-0.5b",
    n_requests: int = 30,
    tokens: int = 6,
    span_s: float = 20.0,
    dt_step: float = 0.25,
    seed: int = 0,
    full_size: bool = False,
) -> dict:
    """Scheduled vs FIFO under chaos, same workload, same engine shape.

    The pool is deliberately tight (2 slots, derived block pool) and the
    offered load exceeds its service rate, so queueing is real; mid-run a
    total channel collapse kills uplinks and a 60% block squeeze starves
    the allocator.  FIFO head-of-line makes interactive requests wait
    behind batch ones; the scheduler preempts/expires instead."""
    import dataclasses

    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=0.1, channel="ge"),
        attn_impl="flash_decode",
    )
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    base_key = jax.random.PRNGKey(seed)
    chaos = ChaosSchedule([
        channel_collapse(0.40 * span_s, 0.60 * span_s, loss_rate=1.0),
        block_pool_squeeze(0.30 * span_s, 0.70 * span_s, fraction=0.6),
    ])
    items = build_sla_workload(
        n_requests, span_s, chaos, cfg.vocab_size,
        sla_classes(tokens, dt_step), seed=seed,
    )
    pool = PoolConfig(
        max_slots=2, max_new=max(8, tokens), max_prompt=8, min_bucket=8,
        paged=True, block_size=4, exhaust_wait_steps=64,
    )
    arms = {}
    for name, scheduled in (("unscheduled", False), ("scheduled", True)):
        booked, eng, sched, exhausted = _drive_sla_arm(
            cfg, params, pool, items, chaos, tokens, dt_step, base_key,
            scheduled,
        )
        served = [it for it in booked if not it["dropped"]]
        arms[name] = {
            "classes": _sla_class_summary(booked),
            "pool_exhausted_signals": exhausted,
            "all_terminal": all(it["req"].terminal for it in served),
            "compiles": eng.compiles,
            "num_buckets": eng.num_buckets,
            "preemptions": sched.stats["preemptions"] if sched else 0,
            "resumes": sched.stats["resumes"] if sched else 0,
            "expired": sched.stats["expired"] if sched else 0,
            "rejected": sched.stats["rejected"] if sched else 0,
            "scheduler_class_report": sched.class_report() if sched else None,
        }
        assert eng.compiles == eng.num_buckets + 1, (
            name, eng.compiles, eng.num_buckets
        )
    hi = "interactive"
    return {
        "bench": "serving_sla",
        "arch": arch,
        "n_requests": n_requests,
        "tokens": tokens,
        "span_virtual_s": span_s,
        "dt_step_virtual_s": dt_step,
        "backend": jax.default_backend(),
        "chaos": [dataclasses.asdict(f) for f in chaos.faults],
        "uplink_dropped": sum(it["dropped"] for it in items),
        "unscheduled": arms["unscheduled"],
        "scheduled": arms["scheduled"],
        "hi_class": hi,
        "hi_hit_rate_unscheduled":
            arms["unscheduled"]["classes"][hi]["deadline_hit_rate"],
        "hi_hit_rate_scheduled":
            arms["scheduled"]["classes"][hi]["deadline_hit_rate"],
        "all_terminal": (arms["unscheduled"]["all_terminal"]
                         and arms["scheduled"]["all_terminal"]),
    }


# ---------------------------------------------------------------------------
# --sharded-serve mode: one logical slot pool over the host mesh
# ---------------------------------------------------------------------------


def run_sharded_bench(
    arch: str = "qwen1.5-0.5b",
    n_requests: int = 24,
    tokens: int = 8,
    lengths=(5, 7, 11, 14),
    loss_rate: float = 0.1,
    channel: str = "ge",
    seed: int = 0,
    full_size: bool = False,
    num_shards: int = 0,
    span_s: float = 12.0,
    dt_step: float = 0.25,
) -> dict:
    """Single slot pool vs the sharded router at EQUAL per-shard pool
    size, plus a mixed-SLA Poisson workload through the router-fronted
    scheduler.

    Phase 1 (throughput): a saturated mixed-length replay through (a) one
    ``ContinuousEngine`` and (b) a ``ShardedEngine`` with one identically
    sized pool per device — same request keys, so the two arms must emit
    IDENTICAL greedy tokens (cross-checked), and each shard must hold the
    engine's compile contract (``compiles == num_buckets + 1``; the
    replay itself runs under ``no_recompile``).  The aggregate-throughput
    gate (``--assert-min-sharded-speedup``) needs real parallel devices —
    CI forces them with ``--xla_force_host_platform_device_count``.

    Phase 2 (SLA through the router): the ``--sla`` driver's virtual-time
    Poisson workload (interactive / standard / batch classes), scheduler
    attached to the ROUTER — per-class p50/p99 and deadline hit-rates
    come out of the identical bookkeeping as the single-engine SLA bench.
    """
    import dataclasses

    from repro.launch.mesh import host_devices

    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=loss_rate,
                                 channel=channel),
        attn_impl="flash_decode",
    )
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    base_key = jax.random.PRNGKey(seed)
    devices = host_devices()
    if num_shards:
        devices = [devices[i % len(devices)] for i in range(num_shards)]

    rng = np.random.RandomState(seed)
    prompts = [
        rng.randint(0, cfg.vocab_size,
                    size=(int(lengths[i % len(lengths)]),)).astype(np.int32)
        for i in range(n_requests)
    ]
    pool = PoolConfig(
        max_slots=4, max_new=max(8, tokens),
        max_prompt=max(int(max(lengths)), 8),
    )

    # ---- single-pool arm (equal per-shard size) ---------------------------
    single = ContinuousEngine(cfg, pool)
    reqs_single, wall_single = _replay(single, params, prompts, tokens,
                                       base_key)
    assert single.compiles == single.num_buckets + 1, (
        single.compiles, single.num_buckets
    )

    # ---- sharded arm ------------------------------------------------------
    sharded = ShardedEngine(cfg, pool, devices=devices)
    sharded.warm(params, [len(p) for p in prompts])
    t0 = time.perf_counter()
    with no_recompile(engines=(sharded, *sharded.shards)):
        reqs_sharded = [
            sharded.submit(p, tokens, key=jax.random.fold_in(base_key, i))
            for i, p in enumerate(prompts)
        ]
        sharded.run(params)
    wall_sharded = time.perf_counter() - t0
    for i, sh in enumerate(sharded.shards):
        assert sh.compiles == sh.num_buckets + 1, (
            i, sh.compiles, sh.num_buckets
        )
    # Same keys -> placement-invariant greedy outputs: the router must
    # emit exactly the single pool's tokens, whatever shard served each.
    for rs, rr in zip(reqs_single, reqs_sharded):
        np.testing.assert_array_equal(rs.tokens, rr.tokens)

    tps_single = n_requests * tokens / wall_single
    tps_sharded = n_requests * tokens / wall_sharded
    shard_stats = sharded.stats()

    # ---- SLA workload through the router-fronted scheduler ----------------
    chaos = ChaosSchedule([])
    items = build_sla_workload(
        n_requests, span_s, chaos, cfg.vocab_size,
        sla_classes(tokens, dt_step), seed=seed,
    )
    pool_sla = PoolConfig(
        max_slots=2, max_new=max(8, tokens), max_prompt=8, min_bucket=8,
        paged=True, block_size=4, exhaust_wait_steps=64,
    )
    booked, eng_sla, sched, _ = _drive_sla_arm(
        cfg, params, pool_sla, items, chaos, tokens, dt_step, base_key,
        scheduled=True,
        make_engine=lambda: ShardedEngine(cfg, pool_sla, devices=devices),
    )
    served = [it for it in booked if not it["dropped"]]
    for i, sh in enumerate(eng_sla.shards):
        assert sh.compiles == sh.num_buckets + 1, (
            i, sh.compiles, sh.num_buckets
        )

    return {
        "bench": "serving_sharded",
        "arch": arch,
        "n_requests": n_requests,
        "tokens": tokens,
        "num_shards": sharded.num_shards,
        "devices": [str(d) for d in devices],
        "prompt_lengths": sorted(set(int(len(p)) for p in prompts)),
        "loss_rate": loss_rate,
        "channel": channel,
        "backend": jax.default_backend(),
        "pool_per_shard": {
            "max_slots": pool.max_slots, "max_new": pool.max_new,
            "max_prompt": pool.max_prompt,
        },
        "single": {
            "tokens_per_s": tps_single,
            "wall_s": wall_single,
            "compiles": single.compiles,
            "num_buckets": single.num_buckets,
        },
        "sharded": {
            "tokens_per_s": tps_sharded,
            "wall_s": wall_sharded,
            "compiles_total": sharded.compiles,
            "per_shard": {
                f"shard{i}": {
                    "compiles": sh.compiles,
                    "num_buckets": sh.num_buckets,
                    "placements": sharded.placement_counts[i],
                }
                for i, sh in enumerate(sharded.shards)
            },
            **{k: v for k, v in shard_stats.items()
               if not k.startswith("shard")},
        },
        "sharded_speedup": tps_sharded / max(tps_single, 1e-9),
        "tokens_identical_across_arms": True,
        "sla_through_router": {
            "classes": _sla_class_summary(booked),
            "all_terminal": all(it["req"].terminal for it in served),
            "preemptions": sched.stats["preemptions"],
            "resumes": sched.stats["resumes"],
            "expired": sched.stats["expired"],
            "rejected": sched.stats["rejected"],
            "placements_per_shard": list(eng_sla.placement_counts),
        },
    }


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHITECTURES))
    ap.add_argument("--clients", type=int, default=24)
    ap.add_argument("--rate", type=float, default=1.0)
    ap.add_argument("--duration", type=float, default=1.0)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--loss-rate", type=float, default=0.1)
    ap.add_argument("--channel", default="iid",
                    choices=["iid", "ge", "gilbert_elliott", "fading"])
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced CPU preset: 3 prompt lengths (3 buckets), 8 tokens",
    )
    ap.add_argument(
        "--paged", action="store_true",
        help="density mode: contiguous vs paged block pool at equal cache "
             "HBM (writes BENCH_serving_paged.json by default)",
    )
    ap.add_argument(
        "--assert-min-sustained-ratio", type=float, default=None,
        help="[--paged] fail unless paged sustains >= RATIO x the "
             "contiguous engine's median in-flight requests",
    )
    ap.add_argument(
        "--sharded-serve", action="store_true",
        help="sharded-router mode: single pool vs one pool per device at "
             "equal per-shard size (cross-checked token-identical), plus "
             "the mixed-SLA workload through the router-fronted scheduler "
             "(writes BENCH_serving_sharded.json by default)",
    )
    ap.add_argument(
        "--num-shards", type=int, default=0,
        help="[--sharded-serve] shard count (0 = one per visible device; "
             "force devices with XLA_FLAGS="
             "--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--assert-min-sharded-speedup", type=float, default=None,
        help="[--sharded-serve] fail unless the sharded arm's aggregate "
             "tokens/s is >= RATIO x the single pool's (needs real "
             "parallel devices — a CI gate, meaningless on one core)",
    )
    ap.add_argument(
        "--sla", action="store_true",
        help="SLA/chaos mode: mixed-class virtual-time workload with a "
             "mid-run channel collapse + block squeeze, scheduled vs FIFO "
             "(writes BENCH_serving_sla.json by default)",
    )
    ap.add_argument("--span", type=float, default=20.0,
                    help="[--sla] virtual arrival span in seconds")
    ap.add_argument("--dt-step", type=float, default=0.25,
                    help="[--sla] virtual seconds per engine step")
    ap.add_argument(
        "--assert-all-terminal", action="store_true",
        help="[--sla] fail unless every served request resolves as "
             "completed|expired|rejected in BOTH arms",
    )
    ap.add_argument(
        "--assert-min-hi-hit-rate", type=float, default=None,
        help="[--sla] fail unless the scheduled arm's high-priority "
             "deadline-hit-rate is >= this floor",
    )
    ap.add_argument(
        "--assert-scheduled-beats-unscheduled", action="store_true",
        help="[--sla] fail unless the scheduled high-priority hit-rate "
             "strictly beats the unscheduled arm's",
    )
    ap.add_argument("--out", default=None)
    ap.add_argument("--assert-max-compiles", type=int, default=None,
                    help="fail if the engine built more XLA programs than this")
    ap.add_argument("--assert-zero-steady-compiles", action="store_true")
    ap.add_argument("--assert-min-rps", type=float, default=None)
    ap.add_argument("--assert-min-speedup", type=float, default=None)
    ap.add_argument(
        "--obs-dir", default=None,
        help="enable the obs registry and write obs_events.jsonl / "
             "obs_metrics.prom artifacts here",
    )
    ap.add_argument(
        "--profile-dir", default=None,
        help="wrap the run in jax.profiler.trace (TensorBoard / xprof dump): "
             "the serve.* spans on the host timeline and the di_* / stack_* "
             "scopes on the device's ops, on one clock",
    )
    ap.add_argument(
        "--assert-obs-span-chain", action="store_true",
        help="fail unless >= 1 request has a complete submit->retire "
             "span chain in the obs event log (implies --obs-dir)",
    )
    ap.add_argument(
        "--assert-obs-drop-rate", action="store_true",
        help="fail unless the engine's realized on-device drop rate is > 0",
    )
    args = ap.parse_args()
    if args.out is None:
        args.out = (
            "BENCH_serving_sharded.json" if args.sharded_serve
            else "BENCH_serving_sla.json" if args.sla
            else "BENCH_serving_paged.json" if args.paged
            else "BENCH_serving.json"
        )

    if args.obs_dir or args.assert_obs_span_chain:
        obs.enable()
    if args.obs_dir:
        import os

        os.makedirs(args.obs_dir, exist_ok=True)

    if args.sharded_serve:
        result = run_sharded_bench(
            arch=args.arch,
            n_requests=args.clients,
            tokens=8 if args.smoke else args.tokens,
            full_size=args.full_size,
            num_shards=args.num_shards,
            span_s=args.span,
            dt_step=args.dt_step,
        )
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        sh, sg = result["sharded"], result["single"]
        sla = result["sla_through_router"]
        logger.info(
            f"serving_bench --sharded-serve[{result['arch']} "
            f"reqs={result['n_requests']} shards={result['num_shards']}]: "
            f"single {sg['tokens_per_s']:.1f} tok/s "
            f"({sg['compiles']} compiles) -> sharded "
            f"{sh['tokens_per_s']:.1f} tok/s "
            f"({result['sharded_speedup']:.2f}x, per-shard compiles "
            + "/".join(str(v["compiles"])
                       for v in sh["per_shard"].values())
            + f") | SLA via router: preempt {sla['preemptions']}, "
            f"resume {sla['resumes']}, placements "
            f"{sla['placements_per_shard']} -> {args.out}"
        )
        ok = True
        if args.assert_min_sharded_speedup is not None and \
                result["sharded_speedup"] < args.assert_min_sharded_speedup:
            logger.error(
                f"ASSERT FAILED: sharded speedup "
                f"{result['sharded_speedup']:.2f}x < "
                f"{args.assert_min_sharded_speedup}"
            )
            ok = False
        if not result["sla_through_router"]["all_terminal"]:
            logger.error("ASSERT FAILED: some router-scheduled requests "
                         "never resolved terminally")
            ok = False
        raise SystemExit(0 if ok else 1)

    if args.sla:
        result = run_sla_bench(
            arch=args.arch,
            n_requests=args.clients,
            tokens=8 if args.smoke else args.tokens,
            span_s=args.span,
            dt_step=args.dt_step,
            full_size=args.full_size,
        )
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        sc, un = result["scheduled"], result["unscheduled"]
        logger.info(
            f"serving_bench --sla[{result['arch']} "
            f"reqs={result['n_requests']}]: uplink dropped "
            f"{result['uplink_dropped']} in collapse | "
            f"{result['hi_class']} hit-rate FIFO "
            f"{result['hi_hit_rate_unscheduled']:.2f} -> scheduled "
            f"{result['hi_hit_rate_scheduled']:.2f} "
            f"(preempt {sc['preemptions']}, resume {sc['resumes']}, "
            f"expire {sc['expired']}, reject {sc['rejected']}; FIFO "
            f"PoolExhausted x{un['pool_exhausted_signals']}) | compiles "
            f"{un['compiles']}/{sc['compiles']} -> {args.out}"
        )
        ok = True
        if args.assert_all_terminal and not result["all_terminal"]:
            logger.error("ASSERT FAILED: some served requests never "
                         "resolved terminally")
            ok = False
        if args.assert_min_hi_hit_rate is not None and \
                result["hi_hit_rate_scheduled"] < args.assert_min_hi_hit_rate:
            logger.error(
                f"ASSERT FAILED: scheduled {result['hi_class']} hit-rate "
                f"{result['hi_hit_rate_scheduled']:.2f} < "
                f"{args.assert_min_hi_hit_rate}"
            )
            ok = False
        if args.assert_scheduled_beats_unscheduled and not (
                result["hi_hit_rate_scheduled"]
                > result["hi_hit_rate_unscheduled"]):
            logger.error(
                f"ASSERT FAILED: scheduled hit-rate "
                f"{result['hi_hit_rate_scheduled']:.2f} does not beat "
                f"unscheduled {result['hi_hit_rate_unscheduled']:.2f}"
            )
            ok = False
        raise SystemExit(0 if ok else 1)

    if args.paged:
        result = run_paged_bench(
            arch=args.arch,
            n_requests=args.clients,
            tokens=8 if args.smoke else args.tokens,
            loss_rate=args.loss_rate,
            channel=args.channel,
            full_size=args.full_size,
        )
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        c, p = result["contiguous"], result["paged"]
        logger.info(
            f"serving_bench --paged[{result['arch']} "
            f"reqs={result['n_requests']}]: equal-HBM "
            f"{result['equal_hbm_bytes']['paged'] / 1e6:.2f} MB — contiguous "
            f"sustains {c['sustained_in_flight']:.0f} in-flight "
            f"({c['max_slots']} slots), paged {p['sustained_in_flight']:.0f} "
            f"({p['max_slots']} slots, {p['pool_blocks_total']:.0f} blocks) "
            f"-> {result['sustained_ratio']:.1f}x density | admission copy "
            f"{result['admission_write_bytes']['contiguous_any_bucket']} B "
            f"-> {result['admission_write_bytes']['paged_bucket_8']} B "
            f"(bucket 8) | compiles {c['compiles']}/{p['compiles']} "
            f"-> {args.out}"
        )
        ok = True
        if args.assert_min_sustained_ratio is not None and \
                result["sustained_ratio"] < args.assert_min_sustained_ratio:
            logger.error(
                f"ASSERT FAILED: sustained ratio "
                f"{result['sustained_ratio']:.2f}x < "
                f"{args.assert_min_sustained_ratio}"
            )
            ok = False
        raise SystemExit(0 if ok else 1)

    kw = {}
    if args.smoke:
        kw = dict(lengths=(6, 12, 24), tokens=8, duration_s=0.5)
    with exporters.jax_profile(args.profile_dir):
        result = run_bench(
            arch=args.arch,
            n_clients=args.clients,
            rate_hz=args.rate,
            duration_s=kw.pop("duration_s", args.duration),
            tokens=kw.pop("tokens", args.tokens),
            max_slots=args.max_slots,
            loss_rate=args.loss_rate,
            channel=args.channel,
            full_size=args.full_size,
            **kw,
        )
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    eng, ref = result["engine"], result["whole_generation"]
    logger.info(
        f"serving_bench[{result['arch']} reqs={result['n_requests']} "
        f"buckets={result['buckets']}]: engine {eng['tokens_per_s']:.1f} tok/s "
        f"({eng['requests_per_s']:.1f} req/s, occ {eng['slot_occupancy']:.2f}, "
        f"compiles {eng['compiles_total']} = {eng['compiles_warmup']} warm-up "
        f"+ {eng['compiles_steady']} steady) | whole-gen "
        f"{ref['tokens_per_s']:.1f} tok/s warm / {ref['tokens_per_s_cold']:.1f} "
        f"cold ({ref['signatures_compiled']} signatures) | speedup "
        f"{result['speedup']:.1f}x warm, {result['speedup_vs_cold']:.1f}x cold "
        f"-> {args.out}"
    )

    if args.obs_dir:
        import os

        os.makedirs(args.obs_dir, exist_ok=True)
        reg = obs.registry()
        exporters.write_jsonl(reg, os.path.join(args.obs_dir, "obs_events.jsonl"))
        exporters.write_prometheus(
            reg, os.path.join(args.obs_dir, "obs_metrics.prom")
        )
        logger.info(f"obs artifacts -> {args.obs_dir}/")

    ok = True
    if args.assert_max_compiles is not None and \
            eng["compiles_total"] > args.assert_max_compiles:
        logger.error(f"ASSERT FAILED: {eng['compiles_total']} compiles > "
              f"{args.assert_max_compiles}")
        ok = False
    if args.assert_zero_steady_compiles and eng["compiles_steady"] != 0:
        logger.error(f"ASSERT FAILED: {eng['compiles_steady']} steady-state compiles")
        ok = False
    if args.assert_min_rps is not None and \
            eng["requests_per_s"] < args.assert_min_rps:
        logger.error(f"ASSERT FAILED: {eng['requests_per_s']:.2f} req/s < "
              f"{args.assert_min_rps}")
        ok = False
    if args.assert_min_speedup is not None and \
            result["speedup"] < args.assert_min_speedup:
        logger.error(f"ASSERT FAILED: speedup {result['speedup']:.2f}x < "
              f"{args.assert_min_speedup}")
        ok = False
    if args.assert_obs_span_chain:
        chains = exporters.request_chain_rids(obs.registry())
        if not chains:
            logger.error("ASSERT FAILED: no complete submit->retire span chain")
            ok = False
        else:
            logger.info(f"obs span chains: {len(chains)} complete requests")
    if args.assert_obs_drop_rate:
        rate = result["engine"]["device"]["realized_drop_rate"]
        if not rate > 0.0:
            logger.error(
                f"ASSERT FAILED: realized on-device drop rate {rate} not > 0"
            )
            ok = False
        else:
            logger.info(f"realized on-device drop rate: {rate:.4f}")
    raise SystemExit(0 if ok else 1)


def run_bench_entry():  # console-script style alias
    main()


if __name__ == "__main__":
    main()
