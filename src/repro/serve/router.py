"""Sharded serving: one logical slot pool spanning the host mesh.

``ContinuousEngine`` is single-device by construction — its slot pool,
block allocator, and AOT ``Compiled`` executables all live on one chip,
so aggregate tokens/s is capped by that chip no matter how many devices
the mesh has.  ``ShardedEngine`` lifts that cap with the standard
router-over-replicas topology:

* one ``ContinuousEngine(device=dev)`` per mesh device ("shard"), each
  holding its own slot/block pool and its own per-device ``Compiled``
  prefill/decode executables (per-shard compiles stay exactly
  ``num_buckets + 1``; the executables are device-pinned, so the
  steady-state zero-recompile contract holds per shard);
* a host-side **occupancy-aware router** that places each admission on
  the shard with the most free capacity — among shards that can admit
  the request at all (a free slot, and — paged — enough free blocks),
  pick the one maximizing ``(free_slots, free_blocks, -shard_idx)``.
  The ``-shard_idx`` tiebreak makes placement fully deterministic;
* the **same engine surface** the single-device pool exposes
  (``submit`` / ``try_admit`` / ``preempt_slot`` / ``running_slots`` /
  ``free_slot_count`` / ``free_block_count`` / ``blocks_held`` /
  ``blocks_needed`` / ``step`` / ``run``), with slots numbered globally
  (``gslot = shard_idx * max_slots + local_slot``), so
  ``SLAScheduler.tick()`` probes the router exactly as it probes one
  engine — preemption picks a global slot, the router forwards to the
  owning shard, and the freed request may resume on a DIFFERENT shard
  (the keyed computation is deterministic in the request key, so
  cross-shard resume stays greedy token-identical; regression-tested
  under iid + GE + int8).

Exactness is placement-invariant by construction: every request runs
the identical batch-1 keyed math whichever shard admits it, because the
shards are full replicas (same params, same pool config, same
programs) and requests never share RNG or link state.

Aggregation semantics where one pool's scalar answer has no exact
multi-pool equivalent:

* ``free_slot_count`` — SUM over shards (a request needs one slot on
  ANY shard, and the scheduler only tests ``> 0``);
* ``free_block_count()`` — MAX over shards: one admission lands on one
  shard, so the best single shard is what decides admissibility.  The
  scheduler's all-or-nothing preemption estimate adds victims' blocks
  across shards to this, which can overestimate what any single shard
  can reach; the result is a wasted preemption round followed by
  backoff (retry), never corruption — ``try_admit`` re-checks the real
  per-shard allocator before committing anything;
* ``PoolExhausted`` typed fields — ``free_slots``/``free_blocks``
  aggregate as sums across shards (the backpressure report describes
  the whole logical pool).

This module is a pure HOST layer over the engines: it reads host
mirrors and drives admission through the public engine API only —
RPA007 (``repro.analysis``) enforces the boundary statically, exactly
as it does for the SLA scheduler and the chaos harness.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.serve.continuous import (
    ContinuousEngine,
    PoolConfig,
    PoolExhausted,
    Request,
    build_request,
)
from repro.serve.scheduler import SLA
from repro.sharding.rules import pool_shard_devices


class ShardedEngine:
    """Occupancy-routed fleet of per-device ``ContinuousEngine`` shards.

    ``mesh=`` (a ``launch.mesh.make_host_mesh`` mesh; its ``model`` axis
    must be size 1 — the slot axis is what shards) or an explicit
    ``devices=`` sequence picks the shard devices; with neither, every
    visible device gets a shard.  ``devices`` may repeat a device —
    tests use several shards on the single CPU device to exercise all
    routing logic in-process without a forced multi-device backend.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        pool: Optional[PoolConfig] = None,
        attn_impl: Optional[str] = None,
        *,
        mesh=None,
        devices: Optional[Sequence] = None,
    ):
        if devices is None:
            devices = (
                pool_shard_devices(mesh) if mesh is not None
                else list(jax.devices())
            )
        devices = list(devices)
        if not devices:
            raise ValueError("ShardedEngine: empty device list")
        self.pool = pool or PoolConfig()
        self.shards: List[ContinuousEngine] = [
            ContinuousEngine(cfg, self.pool, attn_impl, device=dev)
            for dev in devices
        ]
        self.cfg = self.shards[0].cfg        # after any attn_impl override
        self.devices = devices
        self.num_shards = len(devices)
        # Router-level FIFO queue + rid namespace (shard queues stay
        # empty: the router admits through try_admit directly, so the
        # placement decision is always the router's).
        self._queue: collections.deque = collections.deque()
        self._rid = 0
        self.scheduler = None
        self._stalled_steps = 0
        # Placement ledger: admissions per shard, and per-rid placement
        # history (a resumed request appends again — the cross-shard
        # resume tests read this).
        self.placement_counts: List[int] = [0] * self.num_shards
        self.placements: Dict[int, List[int]] = {}
        for sh in self.shards:
            # Completion sink: per-shard completions reach the router's
            # scheduler accounting (and the router gauges) at the
            # shard's sanctioned completion sync point, WITHOUT the
            # shard ticking the scheduler itself.
            sh.completion_sink = self

    # -- aggregate occupancy (the scheduler's probes) ----------------------

    @property
    def active(self) -> int:
        return sum(sh.active for sh in self.shards)

    @property
    def free_slot_count(self) -> int:
        """Free slots across ALL shards (sum — one is enough to admit)."""
        return sum(sh.free_slot_count for sh in self.shards)

    def free_block_count(self) -> int:
        """Free blocks on the BEST single shard (max, not sum): one
        admission lands on one shard, so the most any request can use is
        what one shard can offer.  See the module docstring for how this
        interacts with the scheduler's preemption estimate."""
        return max(sh.free_block_count() for sh in self.shards)

    @property
    def queue_depth(self) -> int:
        if self.scheduler is not None:
            return self.scheduler.queue_depth
        return len(self._queue)

    @property
    def compiles(self) -> int:
        """Total XLA builds across shards (each shard individually holds
        ``compiles == num_buckets + 1`` once its buckets are warm)."""
        return sum(sh.compiles for sh in self.shards)

    @property
    def num_buckets(self) -> int:
        return max(sh.num_buckets for sh in self.shards)

    # -- global slot numbering ---------------------------------------------

    def _locate(self, gslot: int) -> Tuple[int, int]:
        shard_idx, local = divmod(int(gslot), self.pool.max_slots)
        if not 0 <= shard_idx < self.num_shards:
            raise IndexError(
                f"global slot {gslot} out of range for {self.num_shards} "
                f"shard(s) x {self.pool.max_slots} slots"
            )
        return shard_idx, local

    def running_slots(self) -> List[Tuple[int, Request]]:
        """(global_slot, request) over every shard — the preemption-victim
        candidates, exactly the single-engine contract with
        ``gslot = shard_idx * max_slots + local_slot``."""
        out: List[Tuple[int, Request]] = []
        for i, sh in enumerate(self.shards):
            base = i * self.pool.max_slots
            out.extend((base + slot, req) for slot, req in sh.running_slots())
        return out

    def blocks_held(self, gslot: int) -> int:
        shard_idx, local = self._locate(gslot)
        return self.shards[shard_idx].blocks_held(local)

    def blocks_needed(self, prompt_len: int, max_tokens: int) -> int:
        # Identical pool config on every shard — any shard answers.
        return self.shards[0].blocks_needed(prompt_len, max_tokens)

    def preempt_slot(self, gslot: int) -> Request:
        """Evict the request on a global slot (scheduler preemption).
        Re-admission routes through placement again, so the request may
        resume on a different shard — token-identical either way."""
        shard_idx, local = self._locate(gslot)
        req = self.shards[shard_idx].preempt_slot(local)
        self._publish_router_gauges()
        return req

    # -- intake + placement -------------------------------------------------

    def attach_scheduler(self, sched) -> None:
        """Install an SLA scheduler in front of the ROUTER (it probes the
        router, never a shard directly); must happen before traffic."""
        assert not self._queue and self.active == 0, (
            "attach the scheduler before submitting traffic"
        )
        self.scheduler = sched

    def submit(
        self, prompt, max_tokens: int, key: Optional[jax.Array] = None,
        sla: Optional[SLA] = None,
    ) -> Request:
        """Queue one request; returns its handle (filled in by run())."""
        req = build_request(self, self._rid, prompt, max_tokens, key, sla)
        self._rid += 1
        if self.scheduler is not None:
            self.scheduler.enqueue(req)
        else:
            self._queue.append(req)
        obs.registry().counter("serve.requests_submitted").inc()
        return req

    def _place(self, req: Request) -> Optional[int]:
        """Deterministic occupancy-aware placement: among shards that can
        admit ``req`` right now, the one maximizing
        ``(free_slots, free_blocks, -idx)``; None when no shard can."""
        need = (
            self.blocks_needed(req.prompt.size, req.max_tokens)
            if self.pool.paged else 0
        )
        best = None
        best_key = None
        for i, sh in enumerate(self.shards):
            if sh.free_slot_count <= 0:
                continue
            blocks = sh.free_block_count() if self.pool.paged else 0
            if self.pool.paged and blocks < need:
                continue
            k = (sh.free_slot_count, blocks, -i)
            if best_key is None or k > best_key:
                best, best_key = i, k
        return best

    def try_admit(self, params, req: Request) -> bool:
        """Place + admit ONE request; False (no side effects) when no
        shard has the capacity.  The scheduler's tick() probes candidates
        in ITS order through this, exactly as with one engine."""
        idx = self._place(req)
        if idx is None:
            return False
        ok = self.shards[idx].try_admit(params, req)
        if not ok:
            # _place checked the same public occupancy try_admit checks,
            # on the same host mirrors, with no admission in between.
            raise AssertionError(
                f"shard {idx} refused an admission its occupancy allowed"
            )
        self.placement_counts[idx] += 1
        self.placements.setdefault(req.rid, []).append(idx)
        reg = obs.registry()
        reg.counter("router.placements").inc()
        reg.counter(f"router.placements.shard{idx}").inc()
        self._publish_router_gauges()
        return True

    def shard_of(self, req: Request) -> Optional[int]:
        """The shard currently (or last) hosting ``req``, by placement
        history; None before first admission."""
        hist = self.placements.get(req.rid)
        return hist[-1] if hist else None

    # -- driving ------------------------------------------------------------

    def _admit(self, params) -> None:
        # FIFO admission (no scheduler): strict arrival order — the same
        # head-of-line contract as the single engine, with the head
        # probing every shard through _place.
        while self._queue and self.try_admit(params, self._queue[0]):
            self._queue.popleft()

    def step(self, params) -> None:
        """One router tick: admit (scheduler tick when attached, FIFO
        otherwise), then step every shard that has live slots.  Idle
        shards are skipped — an empty pool has nothing to decode."""
        if self.scheduler is not None:
            self.scheduler.tick(self, params)
        else:
            self._admit(params)
        if self.active:
            self._stalled_steps = 0
            for sh in self.shards:
                if sh.active:
                    sh.step(params)
        elif self.scheduler is None and self._queue:
            self._stalled_steps += 1
            if self._stalled_steps > self.pool.exhaust_wait_steps:
                waited, self._stalled_steps = self._stalled_steps, 0
                head = self._queue[0]
                raise PoolExhausted(
                    waited_steps=waited,
                    queued=len(self._queue),
                    # Backpressure report spans the whole logical pool:
                    # sums across shards (free_block_count() is the
                    # admission probe and stays a max).
                    free_slots=self.free_slot_count,
                    free_blocks=sum(
                        sh.free_block_count() for sh in self.shards
                    ),
                    need_blocks=self.blocks_needed(
                        head.prompt.size, head.max_tokens
                    ) if self.pool.paged else 0,
                )
        else:
            self._stalled_steps = 0

    def run(self, params) -> List[Request]:
        """Drive until the queue and every shard are empty; returns every
        request finished since the last run, merged across shards in
        completion order (ties broken by rid).  Same VirtualClock caveat
        as the single engine's run()."""
        reg = obs.registry()
        with reg.span(
            "router.run", queued=len(self._queue), shards=self.num_shards
        ):
            while self._queue or self.active or (
                self.scheduler is not None and self.scheduler.pending
            ):
                self.step(params)
            done: List[Request] = []
            for sh in self.shards:
                done.extend(sh.take_finished())
            done.sort(key=lambda r: (r.t_done, r.rid))
        if reg.enabled:
            self._publish_router_gauges()
            self.publish_device_counters(reg)
        return done

    def harvest(self) -> None:
        """Sync every shard's finished work into host mirrors (the same
        boundary ``ContinuousEngine.harvest`` exposes — external drivers
        call this instead of reaching into shard internals)."""
        for sh in self.shards:
            sh.harvest()

    def warm(self, params, prompt_lens: Sequence[int] = ()) -> None:
        """Compile every needed program on EVERY shard: for each prompt
        length's bucket, admit-and-preempt one throwaway request per
        shard (through the public API, so this also warms the decode
        step and the deaden-slot scatter via the engine's own init).
        After warm(), a steady-state mixed-shard workload over these
        buckets runs under ``analysis.guards.no_recompile`` with zero
        builds, whichever shards the router picks."""
        lens = sorted({int(n) for n in (prompt_lens or (1,))})
        for sh in self.shards:
            for n in lens:
                req = build_request(
                    sh, -1, [1] * n, 1, key=jax.random.PRNGKey(0)
                )
                admitted = sh.try_admit(params, req)
                assert admitted, "warm() needs an idle pool"
                (slot,) = [s for s, r in sh.running_slots() if r is req]
                sh.preempt_slot(slot)

    def on_complete(self, engine, req: Request) -> None:
        """Per-shard completion sink (see ContinuousEngine.completion_sink):
        forward to the scheduler's accounting, then refresh the occupancy
        gauges — the completing shard just freed capacity."""
        if self.scheduler is not None:
            self.scheduler.on_complete(engine, req)
        self._publish_router_gauges()

    # -- observability ------------------------------------------------------

    def _publish_router_gauges(self) -> None:
        """Per-shard occupancy + router queue depth, stamped at the
        existing host sync points (admission / preemption / completion —
        pure host-mirror reads, no device sync)."""
        reg = obs.registry()
        if not reg.enabled:
            return
        reg.gauge("router.queue_depth").set(float(self.queue_depth))
        for i, sh in enumerate(self.shards):
            reg.gauge(f"serve.shard_free_slots.{i}").set(
                float(sh.free_slot_count)
            )
            reg.gauge(f"serve.shard_free_blocks.{i}").set(
                float(sh.free_block_count())
            )

    def device_counters(self) -> Dict[str, float]:
        """Shard device counters summed into one logical-pool view, with
        the realized drop rate re-derived from the summed link totals
        (rates do not sum).  One sync per shard — run-boundary use."""
        total: Dict[str, float] = {}
        for sh in self.shards:
            for k, v in sh.device_counters().items():
                total[k] = total.get(k, 0.0) + v
        total["realized_drop_rate"] = total.get("link_dropped", 0.0) / max(
            total.get("link_elems", 0.0), 1.0
        )
        return total

    def publish_device_counters(self, reg=None) -> Dict[str, float]:
        reg = reg or obs.registry()
        host = self.device_counters()
        for k, v in host.items():
            reg.gauge(f"serve.device.{k}").set(v)
        return host

    def stats(self) -> Dict[str, float]:
        """Aggregate + per-shard counters.  Flat keys (``shard{i}.*``)
        so the bench JSON stays a one-level dict like the engine's."""
        out: Dict[str, float] = {
            "num_shards": float(self.num_shards),
            "compiles": float(self.compiles),
            "num_buckets": float(self.num_buckets),
            "tokens_generated": float(
                sum(sh.tokens_generated for sh in self.shards)
            ),
            "steps": float(sum(sh.steps for sh in self.shards)),
        }
        for i, sh in enumerate(self.shards):
            out[f"shard{i}.compiles"] = float(sh.compiles)
            out[f"shard{i}.num_buckets"] = float(sh.num_buckets)
            out[f"shard{i}.tokens_generated"] = float(sh.tokens_generated)
            out[f"shard{i}.placements"] = float(self.placement_counts[i])
        return out

    # -- one-shot batch API (mirrors ContinuousEngine.generate_batch) -------

    def generate_batch(
        self,
        params,
        prompts,                  # (B, S) int32
        num_tokens: int,
        *,
        key: Optional[jax.Array] = None,
    ):
        """Serve a same-length batch as B independent requests with keys
        ``fold_in(key, i)`` — the single-engine contract, so per request
        the greedy output is token-identical to
        ``generate_reference(prompts[i:i+1], key=fold_in(key, i))``
        regardless of which shard each request lands on."""
        key = key if key is not None else jax.random.PRNGKey(0)
        prompts = jnp.asarray(prompts, jnp.int32)
        b = prompts.shape[0]
        compiles_before = self.compiles
        compile_s_before = sum(sh.compile_s for sh in self.shards)
        reqs = [
            self.submit(prompts[i], num_tokens, key=jax.random.fold_in(key, i))
            for i in range(b)
        ]
        t0 = time.perf_counter()
        self.run(params)
        t_total = time.perf_counter() - t0
        compile_s = sum(sh.compile_s for sh in self.shards) - compile_s_before
        exec_s = max(t_total - compile_s, 1e-9)
        tokens = jnp.stack([jnp.asarray(r.tokens) for r in reqs])
        timings = {
            "generate_s": exec_s,
            "decode_s_per_token": exec_s / max(1, num_tokens),
            "tokens_per_s": (b * num_tokens) / exec_s,
            "compiles": float(self.compiles),
            "compile_s": compile_s,
            "compiled_this_call": float(self.compiles > compiles_before),
            "num_shards": float(self.num_shards),
        }
        return tokens, timings


# ---------------------------------------------------------------------------
# Process-wide router registry (mirrors continuous.pool_engine)
# ---------------------------------------------------------------------------

_ROUTERS: Dict[Tuple, ShardedEngine] = {}
_MAX_ROUTERS = 2      # each router holds num_shards device pools


def sharded_engine(
    cfg: ModelConfig,
    pool: Optional[PoolConfig] = None,
    *,
    num_shards: int = 0,
) -> ShardedEngine:
    """Router per (cfg, pool, num_shards) — pools and compiled programs
    survive across callers.  ``num_shards=0`` spans every visible device.
    On CPU, ``num_shards > len(jax.devices())`` wraps shards around the
    available devices (several pools per device — the in-process test/dev
    mode); on an accelerator it raises, since stacking pools on one chip
    would silently serve a smaller fleet than asked for."""
    pool = pool or PoolConfig()
    k = (cfg, pool, num_shards)
    if k in _ROUTERS:
        _ROUTERS[k] = _ROUTERS.pop(k)          # refresh LRU position
        return _ROUTERS[k]
    devs = list(jax.devices())
    if num_shards > len(devs) and devs[0].platform != "cpu":
        raise ValueError(
            f"{num_shards} shards asked for, but only {len(devs)} "
            f"{devs[0].platform} devices are visible (one shard per device)"
        )
    while len(_ROUTERS) >= _MAX_ROUTERS:
        _ROUTERS.pop(next(iter(_ROUTERS)))
    if num_shards:
        devs = [devs[i % len(devs)] for i in range(num_shards)]
    _ROUTERS[k] = ShardedEngine(cfg, pool, devices=devs)
    return _ROUTERS[k]


def clear_routers() -> None:
    _ROUTERS.clear()
