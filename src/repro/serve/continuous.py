"""Continuous-batching serve engine: slot pool + bucketed prefill.

The whole-generation scan engine (``repro.serve.engine``) compiles one
program per ``(batch, prompt_len, num_tokens)`` signature.  Under live
multi-client traffic — heterogeneous prompt lengths, Poisson arrivals —
that is either a recompile storm (one XLA build per new signature) or
worst-case padding (everyone pays the longest request).  This engine
replaces the execution model with the standard continuous-batching design:

* a persistent **slot pool** — ``max_slots`` independent batch-1 decode
  states (``models.cache.init_slot_pool``) plus per-slot scalars (current
  token, cache length, RNG key chain, generated-token count, budget) and a
  per-slot output buffer, all living on device across requests;
* a **bucketed prefill** program per prompt-length bucket (power-of-two
  padding): runs the padded prompt through the device->link->server stack,
  selects the first token at the request's *true* last position, and
  writes the freshly built batch-1 cache + scalars into a free slot
  (``dynamic_update_slice``; the slot index is data, not shape);
* ONE fused **decode-step** program: ``vmap`` of the per-token DI serve
  step over the slot axis — per-slot cache index, per-slot RNG key chain,
  per-slot lossy-link round, per-slot stop bookkeeping — stepping every
  in-flight request at once.  Requests join and retire mid-flight without
  retracing: admission/retirement only changes slot *data*.

Exactness.  Each slot runs the identical math a batch-1
``generate_reference`` run performs: the prefill's link is the streamed
per-position round (``core.comtune.streamed_channel_link`` — invariant to
right padding), causal attention makes padded positions invisible to real
ones, and the per-slot key chain reproduces the reference's
``key, sub = split(key)`` sequence.  Greedy outputs are token-for-token
identical to ``generate_reference(prompt[None], key=request_key)``
(tests/test_continuous_serve.py, iid + Gilbert-Elliott).

Zero steady-state recompiles.  Every program is AOT-compiled
(``jit(...).lower(...).compile()``) and stored as a ``jax.stages.Compiled``
executable, which *cannot* silently re-trace — a signature mismatch raises.
``engine.compiles`` therefore counts every XLA build exactly: after the
buckets seen by the workload are warm, it equals ``num_buckets + 1`` and
never grows again.

Retired slots keep stepping (their updates are select-masked on the scalar
state, and their cache writes land in positions the attention mask never
reads before the next admission fully overwrites the slot) — masking the
cache too would double the HBM traffic of the hot step for nothing.

Models with recurrent layers (mamba/xLSTM) or sliding windows shorter than
the largest bucket fall back to exact-length buckets: right padding would
leak into their recurrent/rotating state, so each distinct prompt length
compiles its own prefill (still compile-cached and AOT).

Paged mode (``PoolConfig(paged=True)``) swaps the per-slot contiguous
caches for a shared block pool (``models.cache.init_block_pool``) with
per-slot block tables: admission reserves only the blocks a request can
touch instead of a full ``max_seq`` cache, the bucketed prefill copies just
the prompt's blocks into the pool, and the fused decode step follows each
slot's table through the paged flash-decode attention.  Same exactness and
compile contracts as above; see ``_make_paged_decode_step``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.configs.base import ModelConfig
from repro.launch.steps import make_serve_step
from repro.models import attention as attention_lib, cache as cache_lib, lm
from repro.obs import device as obs_device
from repro.serve.engine import abstract_like
from repro.serve.scheduler import SLA


def pow2_bucket(n: int, floor: int = 8) -> int:
    b = max(floor, 1)
    while b < n:
        b *= 2
    return b


def padding_safe(cfg: ModelConfig, max_bucket: int) -> bool:
    """True when right-padding a prompt to ``max_bucket`` cannot change the
    real positions' outputs or decode state: attention-only stacks (causal
    masking ignores right padding) whose sliding windows, if any, are at
    least as long as the largest bucket (so the rotating prefill write
    never evicts real positions because of padding)."""
    for s in cfg.all_layers():
        if s.kind != "attn":
            return False
        if s.window and s.window < max_bucket:
            return False
    return True


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Static shape/behavior of one slot pool (one compile signature).

    ``paged=True`` switches the decode state from ``max_slots`` contiguous
    ``max_seq``-row caches to a shared block pool of ``num_blocks`` x
    ``block_size`` KV rows with per-slot block tables — admission then
    reserves only the blocks a request can actually touch
    (``ceil(min(max(bucket, prompt + max_tokens), max_seq) / block_size)``),
    so ``max_slots`` can exceed what worst-case-contiguous HBM would allow.
    ``num_blocks=0`` derives the worst-case-equivalent pool
    (``max_slots * blocks_per_slot`` + the reserved trash block); set it
    explicitly to oversubscribe.
    """

    max_slots: int = 8
    max_new: int = 64            # per-request generation budget ceiling
    max_prompt: int = 128        # longest admissible prompt
    min_bucket: int = 8          # smallest prefill bucket (power-of-two grid)
    greedy: bool = True
    temperature: float = 1.0
    paged: bool = False
    block_size: int = 16         # KV rows per pool block (paged only)
    num_blocks: int = 0          # physical blocks incl. trash; 0 = derive
    # Backpressure budget when NO scheduler is installed: consecutive
    # no-progress steps (queue non-empty, nothing live, nothing
    # admissible) the engine tolerates before raising PoolExhausted
    # instead of head-of-line blocking forever.
    exhaust_wait_steps: int = 1000

    @property
    def max_bucket(self) -> int:
        return pow2_bucket(self.max_prompt, self.min_bucket)

    @property
    def max_seq(self) -> int:
        return self.max_bucket + self.max_new

    @property
    def blocks_per_slot(self) -> int:
        """Block-table row width: blocks a worst-case request reserves."""
        return -(-self.max_seq // self.block_size)

    @property
    def total_blocks(self) -> int:
        if self.num_blocks:
            return self.num_blocks
        return self.max_slots * self.blocks_per_slot + 1


@dataclasses.dataclass
class Request:
    """One in-flight generation request."""

    rid: int
    prompt: np.ndarray            # (S,) int32
    max_tokens: int
    key: jax.Array                # (2,) uint32 — the per-request RNG chain
    tokens: Optional[np.ndarray] = None   # (max_tokens,) int32 when done
    bucket: int = 0               # prefill bucket this request was padded to
    t_submit: float = 0.0         # queued
    t_admit: float = 0.0          # scheduler picked a slot (before prefill)
    t_first_token: float = 0.0    # prefill produced the first token
    t_done: float = 0.0           # last decode round completed
    t_retire: float = 0.0         # output harvested to host
    # SLA scheduling (repro.serve.scheduler) — defaults are best-effort.
    sla: Optional[SLA] = None
    state: str = "queued"         # queued|running|completed|expired|rejected
    n_preempts: int = 0           # times evicted mid-flight (recompute resume)
    retries: int = 0              # admission attempts that hit backoff
    t_deadline: float = math.inf  # absolute, on the scheduler's clock

    @property
    def done(self) -> bool:
        return self.tokens is not None

    @property
    def terminal(self) -> bool:
        """Terminally resolved: the scheduler will never touch it again."""
        return self.state in ("completed", "expired", "rejected")

    @property
    def ttft_s(self) -> float:
        """Time to first token, from submission (includes queue wait).

        An upper bound.  The engine adds no device sync to stamp it:
        ``t_first_token`` is taken at the first sync it makes anyway after
        the prefill's dispatch (a completion step's ``block_until_ready``
        or a harvest), with the registry on or off alike.  When the
        admitting tick completes a request, as in steady traffic, that is
        the end of the same tick's decode step, so the stamp is at most
        one step late; otherwise it waits for the next such sync."""
        return self.t_first_token - self.t_submit

    @property
    def tpot_s(self) -> float:
        """Mean time per output token over the decode tail (first token
        excluded: it comes from the prefill program)."""
        return (self.t_done - self.t_first_token) / max(1, self.max_tokens - 1)

    @property
    def e2e_s(self) -> float:
        return self.t_done - self.t_submit


class PoolExhausted(RuntimeError):
    """Typed backpressure signal: with no scheduler installed, the engine
    waited ``PoolConfig.exhaust_wait_steps`` consecutive steps with queued
    work, zero live slots, and nothing admissible (e.g. a chaos block
    squeeze holding the pool) — the caller must shed load or free
    capacity instead of the old behavior (head-of-line blocking forever).
    The wait budget re-arms after the raise, so drivers that catch and
    retry get the full budget again."""

    def __init__(self, *, waited_steps: int, queued: int, free_slots: int,
                 free_blocks: int, need_blocks: int):
        self.waited_steps = waited_steps
        self.queued = queued
        self.free_slots = free_slots
        self.free_blocks = free_blocks
        self.need_blocks = need_blocks
        super().__init__(
            f"admission stalled for {waited_steps} steps: {queued} queued, "
            f"{free_slots} free slots, {free_blocks} free blocks "
            f"(head needs {need_blocks}); install an SLAScheduler for "
            "preemption/shedding or free pool capacity"
        )


def build_request(
    eng, rid: int, prompt, max_tokens: int,
    key: Optional[jax.Array] = None, sla: Optional[SLA] = None,
) -> Request:
    """Validate + construct one :class:`Request` against ``eng``'s pool
    limits.  Shared by ``ContinuousEngine.submit`` and the sharded
    router's submit (``repro.serve.router``): the router keeps its own
    rid namespace and queue but admits against identical per-shard
    pools, so the limits — and the impossible-request rejection — are
    the same.  ``eng`` only needs ``.pool`` and ``.blocks_needed``."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    pool = eng.pool
    assert 1 <= prompt.size <= pool.max_prompt, (
        prompt.size, pool.max_prompt
    )
    assert 1 <= max_tokens <= pool.max_new, (max_tokens, pool.max_new)
    if pool.paged:
        # Reject impossible requests at submission: admission blocks
        # head-of-line on a full pool (progress is guaranteed because
        # live requests retire), but a request needing more blocks than
        # the pool HAS would deadlock the queue forever.
        need = eng.blocks_needed(prompt.size, int(max_tokens))
        cap = pool.total_blocks - 1
        if need > cap:
            raise ValueError(
                f"request needs {need} pool blocks (prompt {prompt.size}, "
                f"max_tokens {max_tokens}, block_size "
                f"{pool.block_size}) but the pool only has {cap} "
                "allocatable blocks — it could never be admitted"
            )
    if key is None:
        key = jax.random.PRNGKey(rid)
    return Request(
        rid=rid, prompt=prompt, max_tokens=int(max_tokens),
        key=jnp.asarray(key, jnp.uint32), t_submit=time.perf_counter(),
        sla=sla,
    )


class ContinuousEngine:
    """Slot-pooled continuous-batching engine for one model config.

    The fused decode step vmaps the per-token DI round over the slot axis,
    so with ``cfg.attn_impl`` in {"blockwise", "flash_decode"} (the
    production default) every slot runs the length-masked flash-decode
    attention (``repro.kernels.decode_attention``) with its OWN
    ``cache_index`` — a slot 10 tokens into a 1024-slot cache reads ~1
    KV block instead of all 1024, and int8 caches dequantize inline.
    ``attn_impl`` overrides the config's choice (benchmarks use it to flip
    between the masked path and the ``"naive"`` full-cache oracle without
    re-deriving configs).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        pool: Optional[PoolConfig] = None,
        attn_impl: Optional[str] = None,
        device=None,
    ):
        assert not cfg.frontend, (
            "frontend (VLM/audio) configs are not supported by the slot-pool "
            "engine yet — use the whole-generation DecodeEngine"
        )
        if attn_impl is not None:
            cfg = cfg.with_updates(attn_impl=attn_impl)
        self.cfg = cfg
        self.pool = pool or PoolConfig()
        # ``device`` pins THIS engine's slot pool and all of its AOT
        # executables to one device — the sharded router
        # (``repro.serve.router``) builds one engine per mesh device so a
        # logical pool spans the host mesh.  None keeps the default-device
        # behavior (single-device engines are unchanged).
        self.device = device
        self._placed_params = None
        self._placed_params_id: Optional[int] = None
        if self.pool.paged:
            bad = sorted(
                {s.kind for s in cfg.all_layers() if s.kind != "attn"}
            )
            if bad:
                raise ValueError(
                    f"paged slot pools support attention-only stacks; {cfg.name!r} "
                    f"has {bad} layers (O(1) recurrent state — nothing to page)"
                )
            if self.pool.total_blocks < 2:
                raise ValueError(
                    "paged pool needs >= 2 blocks (block 0 is the trash block)"
                )
        self._padded = padding_safe(cfg, self.pool.max_bucket)
        # Device state + AOT executables (built lazily on first use, since
        # they need the parameter shapes).
        self._state: Optional[Dict[str, Any]] = None
        self._decode_fn = None
        self._prefill_fns: Dict[int, Any] = {}
        # Host-side mirrors (scheduling never reads device memory).
        self._queue: collections.deque = collections.deque()
        self._slot_req: List[Optional[Request]] = [None] * self.pool.max_slots
        self._remaining: List[int] = [0] * self.pool.max_slots
        self._free: List[int] = list(range(self.pool.max_slots))
        self._pending_harvest: List[Tuple[int, Request]] = []
        # Admitted requests whose first token the host has not yet seen
        # (stamped at the next existing device sync; see Request.ttft_s).
        self._awaiting_first: List[Request] = []
        self._finished: List[Request] = []
        self._req_metrics: collections.deque = collections.deque(maxlen=4096)
        self._rid = 0
        # Optional SLA scheduler (repro.serve.scheduler.SLAScheduler):
        # when attached, submit() routes into its ready queue and step()
        # calls its tick() in place of FIFO admission.
        self.scheduler = None
        # Completion sink: an object whose on_complete(engine, req) fires
        # at the completion sync point WITHOUT this engine ticking it.
        # The sharded router installs its scheduler here on every shard —
        # admission routes through the router (placement), but deadline-hit
        # accounting still needs the per-shard completion stamp.
        self.completion_sink = None
        self._stalled_steps = 0
        # Paged-pool host allocator: block 0 is the reserved trash block
        # and is never handed out; free list is LIFO so a freed request's
        # blocks are reused first (stale-row safety is the n_valid mask's
        # job, not the allocator's).
        self._free_blocks: List[int] = (
            list(range(self.pool.total_blocks - 1, 0, -1))
            if self.pool.paged else []
        )
        self._slot_blocks: List[List[int]] = [
            [] for _ in range(self.pool.max_slots)
        ]
        # Counters / stats.
        self.compiles = 0
        self.traces = 0
        self.compile_s = 0.0
        self.steps = 0
        self.busy_slot_steps = 0
        self.tokens_generated = 0
        self.blocks_written = 0
        self.peak_blocks_used = 0
        self.active_per_step: collections.deque = collections.deque(
            maxlen=65536
        )

    # -- static program construction --------------------------------------

    def _dev_ctx(self):
        """Context placing array creation on this engine's device (no-op
        for the default single-device engine)."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def _params_for(self, params):
        """Per-device parameter copy, cached by identity: a device-pinned
        engine must not re-upload the (large) params every dispatch, and
        its ``Compiled`` executables expect inputs resident on its own
        device.  The default engine passes params through untouched."""
        if self.device is None:
            return params
        if self._placed_params_id != id(params):
            self._placed_params = jax.device_put(params, self.device)
            self._placed_params_id = id(params)
        return self._placed_params

    def _aot(self, fn, donate: Tuple[int, ...], avals: Tuple) -> Any:
        """jit -> lower -> compile; returns the Compiled executable and
        bumps the engine-wide compile/trace accounting.  A device-pinned
        engine gives every aval a ``SingleDeviceSharding`` on its device,
        so its executables target that device and no other."""

        def traced(*args):
            self.traces += 1     # Python side effect: fires at trace time
            return fn(*args)

        if self.device is not None:
            here = SingleDeviceSharding(self.device)
            avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here),
                avals,
            )
        t0 = time.perf_counter()
        compiled = jax.jit(traced, donate_argnums=donate).lower(*avals).compile()
        self.compile_s += time.perf_counter() - t0
        self.compiles += 1
        return compiled

    def _init_state(self) -> Dict[str, Any]:
      with self._dev_ctx():
        p = self.pool
        if p.paged:
            cache = cache_lib.init_block_pool(
                self.cfg, p.total_blocks, p.block_size, device=self.device
            )
        else:
            cache = cache_lib.init_slot_pool(
                self.cfg, p.max_slots, p.max_seq, device=self.device
            )
        state = {
            "cache": cache,
            "token": jnp.zeros((p.max_slots, 1, 1), jnp.int32),
            "length": jnp.zeros((p.max_slots,), jnp.int32),
            "key": jnp.zeros((p.max_slots, 2), jnp.uint32),
            "n_gen": jnp.zeros((p.max_slots,), jnp.int32),
            "budget": jnp.zeros((p.max_slots,), jnp.int32),
            "out": jnp.zeros((p.max_slots, p.max_new), jnp.int32),
            # On-device telemetry (obs.DeviceCounters): carried and
            # accumulated UNCONDITIONALLY — whether the host registry is
            # enabled only decides whether anyone reads it, so obs on/off
            # traces byte-identical programs and the compile-count
            # invariant is independent of observability.
            "obs": obs_device.counter_zeros(),
        }
        if p.paged:
            # Per-slot block-table rows (zero-padded: unreserved entries
            # point at the trash block).  Data, not shape — admission and
            # retirement rewrite rows without retracing anything.
            state["block_table"] = jnp.zeros(
                (p.max_slots, p.blocks_per_slot), jnp.int32
            )
        if self.device is not None:
            # Commit the whole tree (``default_device`` only places,
            # commitment keeps follow-the-data dispatches — e.g. the
            # deaden-slot scatter — on THIS shard's device).
            state = jax.device_put(state, self.device)
        return state

    def _make_decode_step(self):
        if self.pool.paged:
            return self._make_paged_decode_step()
        cfg, pool = self.cfg, self.pool
        step = make_serve_step(cfg)
        masked_attn = cfg.attn_impl != "naive"

        def pool_step(params, state):
            def one(token, cache, length, key, n_gen, budget, out_row):
                # Mirrors one iteration of the reference per-token loop at
                # batch 1: emit the carried token, split the slot's key,
                # run the DI round, select the next token.  The link tap
                # is installed INSIDE the vmapped body (an outer collector
                # would leak batch tracers); the per-slot totals ride out
                # as vmap outputs.
                live = n_gen < budget
                with obs_device.tap_link_stats() as tap:
                    if pool.greedy:
                        key2, sub = jax.random.split(key)
                    else:
                        key2, sub, ks = jax.random.split(key, 3)
                    logits, new_cache = step(params, token, cache, length, sub)
                    link = tap.totals()
                with jax.named_scope("di_sample"):
                    if pool.greedy:
                        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(
                            jnp.int32
                        )
                    else:
                        scaled = logits.astype(jnp.float32) / jnp.float32(
                            max(pool.temperature, 1e-6)
                        )
                        nxt = jax.random.categorical(ks, scaled, axis=-1)[
                            :, None
                        ].astype(jnp.int32)
                    out2 = jax.lax.dynamic_update_slice(
                        out_row, token[0], (n_gen,)
                    )
                sel = lambda a, b: jnp.where(live, a, b)
                # NOTE: new_cache is NOT select-masked — a retired slot's
                # dirty write lands at its frozen length (never read past
                # the attention validity mask) and the next admission
                # overwrites the whole slot.  Masking would double the HBM
                # traffic of the hot step.
                return (
                    sel(nxt, token),
                    new_cache,
                    sel(length + 1, length),
                    sel(key2, key),
                    sel(n_gen + 1, n_gen),
                    sel(out2, out_row),
                    link,
                )

            token, cache, length, key, n_gen, out, link = jax.vmap(one)(
                state["token"], state["cache"], state["length"],
                state["key"], state["n_gen"], state["budget"], state["out"],
            )
            # Device counters: only LIVE slots count (retired slots keep
            # stepping, but their rounds belong to no request — exactly
            # the rounds a per-request reference run never performs).
            livef = (state["n_gen"] < state["budget"]).astype(jnp.float32)
            valid = (state["length"] + 1).astype(jnp.float32)
            read_b = cache_lib.decode_read_bytes_jnp(
                cfg, pool.max_seq, valid, masked=masked_attn
            )
            c = state["obs"]
            new_obs = {
                "decode_steps": c["decode_steps"] + jnp.int32(1),
                "valid_tokens": c["valid_tokens"] + jnp.sum(livef * valid),
                "decode_read_bytes": c["decode_read_bytes"]
                + jnp.sum(livef * read_b),
                "link_elems": c["link_elems"] + jnp.sum(livef * link["elems"]),
                "link_dropped": c["link_dropped"]
                + jnp.sum(livef * link["dropped"]),
                "fec_recovered_packets": c["fec_recovered_packets"]
                + jnp.sum(livef * link["fec_recovered"]),
            }
            return {
                "cache": cache, "token": token, "length": length,
                "key": key, "n_gen": n_gen, "budget": state["budget"],
                "out": out, "obs": new_obs,
            }

        return pool_step

    def _make_paged_decode_step(self):
        """The fused decode step over the SHARED block pool.

        The contiguous step vmaps a batch-1 serve step over the slot axis;
        a shared pool cannot be vmapped (every slot scatters into the same
        buffers), so this runs ONE batched forward over all slots instead:
        per-slot lengths become the ``(B, 1)`` position batch, the
        per-slot link rounds come from ``lm.make_slotwise_link_fn`` (an
        inner vmap with per-slot keys — bitwise the draws the vmapped
        engine makes), and the paged attention branch
        (``models.attention`` + ``kernels.decode_attention``) consumes the
        block table through a ``PagedIndex``.  Every op is batch-row
        independent, so per-slot results equal the vmapped form's — the
        token-identity contract vs ``generate_reference`` is unchanged
        (regression-tested under iid + GE + int8).  Scalar-state updates
        are live-masked exactly like the contiguous step; dirty cache
        writes by retired slots are routed to the trash block *inside*
        ``_write_decode_paged`` (with a shared pool they could otherwise
        land in blocks already reallocated to live requests).
        """
        cfg, pool = self.cfg, self.pool

        def pool_step(params, state):
            live = state["n_gen"] < state["budget"]
            if pool.greedy:
                ks = jax.vmap(jax.random.split)(state["key"])    # (B, 2, 2)
                key2, sub, kcat = ks[:, 0], ks[:, 1], None
            else:
                ks = jax.vmap(lambda k: jax.random.split(k, 3))(state["key"])
                key2, sub, kcat = ks[:, 0], ks[:, 1], ks[:, 2]
            pidx = attention_lib.PagedIndex(
                lengths=state["length"],
                block_table=state["block_table"],
                live=live,
                max_seq=pool.max_seq,
                block_size=pool.block_size,
            )
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(
                    state["length"][:, None, None],
                    (pool.max_slots, 3, 1),
                )
            else:
                positions = state["length"][:, None]
            tokens = state["token"][:, 0]                        # (B, 1)
            with obs_device.tap_link_stats() as tap:
                link_fn = lm.make_slotwise_link_fn(
                    cfg, params["link"], sub, "serve", live=live
                )
                logits, new_cache, _ = lm.forward(
                    params, tokens, cfg,
                    positions=positions,
                    cache=state["cache"], cache_index=pidx,
                    link_fn=link_fn, mode="decode",
                )
                link = tap.totals()
            last = logits[:, 0]                                  # (B, V)
            with jax.named_scope("di_sample"):
                if pool.greedy:
                    nxt = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
                else:
                    scaled = last.astype(jnp.float32) / jnp.float32(
                        max(pool.temperature, 1e-6)
                    )
                    nxt = jax.vmap(jax.random.categorical)(kcat, scaled)[
                        :, None
                    ].astype(jnp.int32)
                # Emit the token fed INTO the round (reference-loop order).
                out2 = jax.vmap(
                    lambda row, t, n: jax.lax.dynamic_update_slice(row, t, (n,))
                )(state["out"], tokens[:, 0:1], state["n_gen"])
            livec = live[:, None]
            livef = live.astype(jnp.float32)
            valid = (state["length"] + 1).astype(jnp.float32)
            read_b = cache_lib.decode_read_bytes_jnp(
                cfg, pool.max_seq, valid,
                paged=True, block_size=pool.block_size,
            )
            c = state["obs"]
            new_obs = {
                "decode_steps": c["decode_steps"] + jnp.int32(1),
                "valid_tokens": c["valid_tokens"] + jnp.sum(livef * valid),
                "decode_read_bytes": c["decode_read_bytes"]
                + jnp.sum(livef * read_b),
                # Link totals arrive pre-masked: the slot-wise link fn
                # weights each slot's draws by ``live`` before emitting.
                "link_elems": c["link_elems"] + link["elems"],
                "link_dropped": c["link_dropped"] + link["dropped"],
                "fec_recovered_packets": c["fec_recovered_packets"]
                + link["fec_recovered"],
            }
            return {
                "cache": new_cache,
                "block_table": state["block_table"],
                "token": jnp.where(livec[..., None], nxt[:, :, None],
                                   state["token"]),
                "length": jnp.where(live, state["length"] + 1,
                                    state["length"]),
                "key": jnp.where(livec, key2, state["key"]),
                "n_gen": jnp.where(live, state["n_gen"] + 1, state["n_gen"]),
                "budget": state["budget"],
                "out": jnp.where(livec, out2, state["out"]),
                "obs": new_obs,
            }

        return pool_step

    def _make_prefill(self, bucket: int):
        cfg, pool = self.cfg, self.pool
        # Paged admission writes a STATIC number of blocks per bucket
        # program: the padded prompt occupies ceil(bucket / block_size)
        # blocks (padded rows ride along exactly as in the contiguous slot
        # copy — invisible behind causal masking and n_valid).  True_len
        # stays data; the copy count must be shape-static.
        nb_prompt = min(
            -(-bucket // pool.block_size), pool.blocks_per_slot
        ) if pool.paged else 0

        def prefill(params, state, prompt, true_len, slot, budget, rkey,
                    *rest):
            # Reference chain: key, sub = split(request_key); prefill(sub).
            key, sub = jax.random.split(rkey)
            fresh = cache_lib.init_cache(cfg, 1, pool.max_seq)
            # Link counters for the streamed prompt upload.  NOTE: the
            # streamed link runs over the PADDED bucket, so these totals
            # include the padded positions' draws (they are real rounds of
            # the compiled program; the oracle test replicates the
            # padding).
            with obs_device.tap_link_stats() as tap:
                logits, filled, _ = lm.forward(
                    params, prompt, cfg,
                    cache=fresh, cache_index=0,
                    link_key=sub, link_mode="serve", mode="prefill",
                )
                link = tap.totals()
            with jax.named_scope("di_sample"):
                last = jax.lax.dynamic_slice(
                    logits, (0, true_len - 1, 0), (1, 1, logits.shape[-1])
                )[:, 0]                               # (1, V): true last pos
                if pool.greedy:
                    tok0 = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
                else:
                    key, ks = jax.random.split(key)
                    scaled = last.astype(jnp.float32) / jnp.float32(
                        max(pool.temperature, 1e-6)
                    )
                    tok0 = jax.random.categorical(ks, scaled, axis=-1)[
                        :, None
                    ].astype(jnp.int32)
            set1 = lambda arr, v: arr.at[slot].set(v)
            c = state["obs"]
            new_obs = {
                **c,
                "link_elems": c["link_elems"] + link["elems"],
                "link_dropped": c["link_dropped"] + link["dropped"],
                "fec_recovered_packets": c["fec_recovered_packets"]
                + link["fec_recovered"],
            }
            if pool.paged:
                (bt_row,) = rest
                new_cache = cache_lib.write_prompt_blocks(
                    state["cache"], filled, bt_row, nb_prompt,
                    pool.block_size,
                )
                extra = {
                    "block_table": jax.lax.dynamic_update_slice(
                        state["block_table"], bt_row[None],
                        (slot, jnp.int32(0)),
                    ),
                }
            else:
                new_cache = cache_lib.write_slot(state["cache"], filled, slot)
                extra = {}
            return {
                **extra,
                "obs": new_obs,
                "cache": new_cache,
                "token": jax.lax.dynamic_update_slice(
                    state["token"], tok0[None], (slot, 0, 0)
                ),
                "length": set1(state["length"], true_len),
                "key": set1(state["key"], key),
                "n_gen": set1(state["n_gen"], jnp.int32(0)),
                "budget": set1(state["budget"], budget),
                "out": jax.lax.dynamic_update_slice(
                    state["out"],
                    jnp.zeros((1, pool.max_new), jnp.int32),
                    (slot, 0),
                ),
            }

        return prefill

    def _ensure(self, params) -> None:
        if self._state is None:
            self._state = self._init_state()
            # Warm the deaden-slot scatter (a no-op on the all-zero budget)
            # so a mid-run preemption never compiles anything: the slot
            # index is a device scalar, so ONE cached program serves every
            # slot and the steady state stays build-free.
            self._deaden_slot(0)
        if self._decode_fn is None:
            avals = (abstract_like(params), abstract_like(self._state))
            self._decode_fn = self._aot(self._make_decode_step(), (1,), avals)

    def _prefill_for(self, params, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            avals = (
                abstract_like(params),
                abstract_like(self._state),
                jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                scalar, scalar, scalar,
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            )
            if self.pool.paged:
                avals += (
                    jax.ShapeDtypeStruct(
                        (self.pool.blocks_per_slot,), jnp.int32
                    ),
                )
            fn = self._aot(self._make_prefill(bucket), (1,), avals)
            self._prefill_fns[bucket] = fn
        return fn

    # -- scheduling --------------------------------------------------------

    def bucket_for(self, length: int) -> int:
        if self._padded:
            return pow2_bucket(length, self.pool.min_bucket)
        return length

    @property
    def num_buckets(self) -> int:
        return len(self._prefill_fns)

    @property
    def decode_executable(self):
        """The fused decode step's ``jax.stages.Compiled`` (None until the
        first request is admitted)."""
        return self._decode_fn

    def devices_in_use(self) -> set:
        """Devices that hold this engine's pool state or that its compiled
        programs take their inputs on."""
        devs = {
            d for leaf in jax.tree_util.tree_leaves(self._state)
            for d in leaf.devices()
        }
        for fn in (self._decode_fn, *self._prefill_fns.values()):
            if fn is not None:
                for s in jax.tree_util.tree_leaves(fn.input_shardings):
                    devs |= s.device_set
        return devs

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def free_slot_count(self) -> int:
        return len(self._free)

    def free_block_count(self) -> int:
        """Blocks the host allocator could hand out right now (paged)."""
        return len(self._free_blocks)

    def running_slots(self) -> List[Tuple[int, Request]]:
        """(slot, request) for every in-flight slot — the scheduler's
        preemption-victim candidates (host mirrors only, no device read)."""
        return [
            (slot, req) for slot, req in enumerate(self._slot_req)
            if req is not None
        ]

    def blocks_held(self, slot: int) -> int:
        return len(self._slot_blocks[slot])

    def blocks_needed(self, prompt_len: int, max_tokens: int) -> int:
        """Blocks one request reserves for its whole lifetime: the padded
        prefill rows plus every decode write, capped by the rotation at
        ``max_seq`` (and hence by the block-table row width)."""
        p = self.pool
        rows = min(
            max(self.bucket_for(prompt_len), prompt_len + max_tokens),
            p.max_seq,
        )
        return min(cache_lib.blocks_for(rows, p.block_size), p.blocks_per_slot)

    def attach_scheduler(self, sched) -> None:
        """Install an SLA scheduler; must happen before any traffic (a
        half-FIFO, half-scheduled queue would have no coherent order)."""
        assert not self._queue and self.active == 0, (
            "attach the scheduler before submitting traffic"
        )
        self.scheduler = sched

    def submit(
        self, prompt, max_tokens: int, key: Optional[jax.Array] = None,
        sla: Optional[SLA] = None,
    ) -> Request:
        """Queue one request; returns its handle (filled in by run())."""
        reg = obs.registry()
        with reg.span("serve.submit"):
            req = build_request(self, self._rid, prompt, max_tokens, key, sla)
            self._rid += 1
            if self.scheduler is not None:
                self.scheduler.enqueue(req)
            else:
                self._queue.append(req)
            reg.counter("serve.requests_submitted").inc()
        return req

    def harvest(self) -> None:
        """Read every finished-but-unread output row to the host (one
        device sync for the whole batch).  Public for router/driver use;
        run() calls it at drain."""
        self._harvest()

    def take_finished(self) -> List[Request]:
        """Harvest, then hand over (and clear) the finished-request list.
        The sharded router merges these across shards; run() is the
        single-engine wrapper around the same drain."""
        self._harvest()
        done, self._finished = self._finished, []
        return done

    def _harvest(self) -> None:
        if not self._pending_harvest:
            return
        reg = obs.registry()
        with reg.span("serve.harvest"):
            out = np.asarray(self._state["out"])    # one sync for the batch
            now = time.perf_counter()
            self._stamp_first_tokens(now)
            for slot, req in self._pending_harvest:
                req.tokens = out[slot, : req.max_tokens].copy()
                req.t_retire = now
                self._req_metrics.append(
                    {"ttft_s": req.ttft_s, "tpot_s": req.tpot_s,
                     "e2e_s": req.e2e_s}
                )
                if reg.enabled:
                    self._emit_request_spans(reg, req, slot)
            self._pending_harvest.clear()

    def _stamp_first_tokens(self, now: float) -> None:
        """Called right after a device sync: every prefill dispatched
        before it has produced its first token by ``now``."""
        for req in self._awaiting_first:
            req.t_first_token = now
        self._awaiting_first.clear()

    def _emit_request_spans(self, reg, req: Request, slot: int) -> None:
        """The submit→retire span chain, reconstructed from the stamps
        taken at sync points (one parent span + the four lifecycle
        phases), plus the TTFT/TPOT/e2e histograms."""
        parent = reg.record_span(
            "request", req.t_submit, req.t_retire, rid=req.rid, slot=slot,
            bucket=req.bucket, prompt_len=int(req.prompt.size),
            max_tokens=req.max_tokens, ttft_s=req.ttft_s, tpot_s=req.tpot_s,
        )
        reg.record_span(
            "request/queue", req.t_submit, req.t_admit,
            parent=parent, rid=req.rid,
        )
        reg.record_span(
            "request/prefill", req.t_admit, req.t_first_token,
            parent=parent, rid=req.rid, bucket=req.bucket,
        )
        reg.record_span(
            "request/decode", req.t_first_token, req.t_done,
            parent=parent, rid=req.rid, tokens=req.max_tokens,
        )
        reg.record_span(
            "request/retire", req.t_done, req.t_retire,
            parent=parent, rid=req.rid,
        )
        reg.histogram("serve.ttft_s").observe(req.ttft_s)
        reg.histogram("serve.tpot_s").observe(req.tpot_s)
        reg.histogram("serve.e2e_s").observe(req.e2e_s)
        reg.counter("serve.requests_retired").inc()
        reg.counter("serve.tokens_generated").inc(req.max_tokens)

    def _admit(self, params) -> None:
        # FIFO admission (no scheduler): strict arrival order, so a head
        # that does not fit blocks everyone behind it — progress is
        # guaranteed by retirements, and step() converts a permanent stall
        # into PoolExhausted after the wait budget.
        while self._queue and self.try_admit(params, self._queue[0]):
            self._queue.popleft()

    def try_admit(self, params, req: Request) -> bool:
        """Admit ONE request into a free slot if resources allow; returns
        False (no side effects) when there is no free slot or — paged —
        not enough free blocks.  The scheduler's tick() probes candidates
        in ITS order through this; FIFO _admit() probes only the head."""
        p = self.pool
        # A router-fronted shard sees try_admit before any step(): make
        # sure the pool exists, and dispatch against this shard's own
        # parameter copy (no-ops for the default single-device engine).
        self._ensure(params)
        params = self._params_for(params)
        if not self._free:
            return False
        need = 0
        if p.paged:
            # Pool-exhaustion gate BEFORE committing to the admission: a
            # full pool refuses (live slots never lose blocks here;
            # retirements — or the scheduler's preemptions — free some)
            # instead of partially admitting or stealing from a live slot.
            need = self.blocks_needed(req.prompt.size, req.max_tokens)
            if need > len(self._free_blocks):
                return False
        with obs.registry().span("serve.admit"):
            self._admit_into_slot(params, req, need)
        return True

    def _admit_into_slot(self, params, req: Request, need: int) -> None:
        """The admission itself, once ``try_admit`` has found room: read
        any finished rows first, upload the prompt and scalars, dispatch
        the bucket's prefill."""
        p = self.pool
        if self._pending_harvest:
            # A freed slot's output row is about to be zeroed: read the
            # finished requests first (one host sync for all of them).
            self._harvest()
        slot = self._free.pop()
        bucket = self.bucket_for(req.prompt.size)
        fn = self._prefill_for(params, bucket)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : req.prompt.size] = req.prompt
        req.bucket = bucket
        extra = ()
        if p.paged:
            blocks = [self._free_blocks.pop() for _ in range(need)]
            self._slot_blocks[slot] = blocks
            bt_row = np.zeros((p.blocks_per_slot,), np.int32)
            bt_row[: len(blocks)] = blocks
            extra = (jnp.asarray(bt_row),)
        # Admission is the scheduling decision, so stamp it BEFORE the
        # prefill dispatch — the old after-dispatch stamp folded the
        # prefill into the "queue wait" phase and made TTFT's prefill
        # component unmeasurable.
        req.t_admit = time.perf_counter()
        self._state = fn(
            params, self._state, jnp.asarray(padded),
            jnp.asarray(req.prompt.size, jnp.int32),
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(req.max_tokens, jnp.int32),
            req.key,
            *extra,
        )
        self._slot_req[slot] = req
        self._remaining[slot] = req.max_tokens
        req.state = "running"
        if p.paged:
            nb = min(
                cache_lib.blocks_for(bucket, p.block_size),
                p.blocks_per_slot,
            )
            self.blocks_written += nb
            used = sum(len(b) for b in self._slot_blocks)
            self.peak_blocks_used = max(self.peak_blocks_used, used)
            obs.registry().counter("serve.blocks_written").inc(nb)
            self._publish_pool_gauges()
        self._awaiting_first.append(req)

    def _deaden_slot(self, slot: int) -> None:
        """Zero a slot's generation budget on device: the decode step's
        live mask (``n_gen < budget``) stops its scalar updates, and — in
        paged mode — routes its cache writes to the trash block.  The slot
        index is a device scalar so ONE cached scatter serves every slot
        (warmed at state init; preemption never builds a program)."""
        self._state["budget"] = (
            self._state["budget"].at[jnp.asarray(slot, jnp.int32)].set(0)
        )

    def preempt_slot(self, slot: int) -> Request:
        """Evict the slot's in-flight request (scheduler preemption):
        recompute-on-resume, vLLM-style.  Deaden the slot on device FIRST
        — once its blocks return to the allocator they can be handed to
        the very next admission, and a still-live slot would keep writing
        through its stale block table into them.  Then release the host
        mirrors; re-admission replays the request from scratch under the
        same key, so the resumed run is greedy token-identical to an
        uninterrupted one."""
        req = self._slot_req[slot]
        assert req is not None, f"slot {slot} has no in-flight request"
        self._deaden_slot(slot)
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        self._free.append(slot)
        if self.pool.paged:
            self._free_blocks.extend(reversed(self._slot_blocks[slot]))
            self._slot_blocks[slot] = []
            self._publish_pool_gauges()
        req.state = "queued"
        req.n_preempts += 1
        obs.registry().counter("serve.preemptions").inc()
        return req

    def _pool_fragmentation(self) -> float:
        """Internal fragmentation of the live reservations: 1 − (rows
        holding real tokens) / (rows reserved), over live slots.  0.0 with
        nothing live."""
        bs = self.pool.block_size
        reserved = valid = 0
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            nres = len(self._slot_blocks[slot]) * bs
            done_toks = req.max_tokens - self._remaining[slot]
            valid += min(int(req.prompt.size) + done_toks, nres)
            reserved += nres
        if reserved == 0:
            return 0.0
        return 1.0 - valid / reserved

    def _publish_pool_gauges(self) -> None:
        """Paged-pool occupancy gauges, set at the existing host sync
        points (admission / retirement — pure host-mirror reads, no extra
        device sync)."""
        reg = obs.registry()
        reg.gauge("serve.pool_blocks_total").set(
            float(self.pool.total_blocks - 1)
        )
        reg.gauge("serve.pool_blocks_used").set(
            float(sum(len(b) for b in self._slot_blocks))
        )
        reg.gauge("serve.pool_fragmentation").set(self._pool_fragmentation())

    def _decode_once(self, params) -> None:
        self.active_per_step.append(self.active)
        params = self._params_for(params)
        reg = obs.registry()
        with reg.span("serve.decode"):
            self._state = self._decode_fn(params, self._state)
        self.steps += 1
        completed = []
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            self.busy_slot_steps += 1
            self.tokens_generated += 1
            self._remaining[slot] -= 1
            if self._remaining[slot] == 0:
                completed.append((slot, req))
                self._slot_req[slot] = None
                self._free.append(slot)
                if self.pool.paged:
                    # LIFO free: the retired request's blocks go back in
                    # reverse so the next admission reuses them first.
                    self._free_blocks.extend(
                        reversed(self._slot_blocks[slot])
                    )
                    self._slot_blocks[slot] = []
        if completed and self.pool.paged:
            self._publish_pool_gauges()
        if completed:
            # Block before stamping t_done: dispatch is async, so a
            # dispatch-time stamp would under-report completion latency
            # whenever execution lags the host (the sync only happens on
            # completion steps, so steady-state steps still pipeline).
            with reg.span("serve.sync"):
                jax.block_until_ready(self._state["out"])  # noqa: RPA005 — sanctioned sync point (completion steps only; steady steps pipeline)
            now = time.perf_counter()
            self._stamp_first_tokens(now)
            for slot, req in completed:
                req.t_done = now
                req.state = "completed"
                self._pending_harvest.append((slot, req))
                self._finished.append(req)
                sched = self.scheduler or self.completion_sink
                if sched is not None:
                    # Deadline-hit accounting rides the sanctioned
                    # completion sync above — no extra device read.
                    sched.on_complete(self, req)

    def step(self, params) -> None:
        """One engine tick: admit (scheduler tick when one is attached,
        FIFO otherwise), then run one fused decode step over the pool (if
        anything is live).  Unscheduled no-progress stalls are bounded by
        ``PoolConfig.exhaust_wait_steps`` → ``PoolExhausted``."""
        with obs.registry().span("serve.step"):
            self._ensure(params)
            if self.scheduler is not None:
                self.scheduler.tick(self, params)
            else:
                self._admit(params)
            if self.active:
                self._stalled_steps = 0
                self._decode_once(params)
            elif self.scheduler is None and self._queue:
                self._stalled_steps += 1
                if self._stalled_steps > self.pool.exhaust_wait_steps:
                    waited, self._stalled_steps = self._stalled_steps, 0
                    head = self._queue[0]
                    raise PoolExhausted(
                        waited_steps=waited,
                        queued=len(self._queue),
                        free_slots=len(self._free),
                        free_blocks=len(self._free_blocks),
                        need_blocks=self.blocks_needed(
                            head.prompt.size, head.max_tokens
                        ) if self.pool.paged else 0,
                    )
            else:
                self._stalled_steps = 0

    def run(self, params) -> List[Request]:
        """Drive until the queue and the pool are empty; returns every
        request finished since the last run (harvested, ``tokens`` filled).
        With a scheduler attached, also drains its ready/retry queues —
        requests it expires or rejects resolve terminally without tokens
        (check ``req.state``).  NOTE: a scheduler on a ``VirtualClock``
        must be driven by step()+advance() instead; run() never advances
        virtual time, so future retry deadlines would spin forever."""
        reg = obs.registry()
        with reg.span("engine.run", queued=len(self._queue)):
            self._ensure(params)
            while self._queue or self.active or (
                self.scheduler is not None and self.scheduler.pending
            ):
                self.step(params)
            done = self.take_finished()
        if reg.enabled:
            self.publish_device_counters(reg)
        return done

    def device_counters(self) -> Dict[str, float]:
        """The on-device ``obs.DeviceCounters`` pytree as host floats plus
        the derived realized drop rate.  One sync — call at run/epoch
        boundaries, not per step."""
        if self._state is None:
            host = {k: 0.0 for k in obs_device.COUNTER_KEYS}
            host["realized_drop_rate"] = 0.0
            return host
        return obs_device.counters_to_host(self._state["obs"])

    def publish_device_counters(self, reg=None) -> Dict[str, float]:
        """Harvest the device counters into registry gauges."""
        reg = reg or obs.registry()
        host = self.device_counters()
        for k, v in host.items():
            reg.gauge(f"serve.device.{k}").set(v)
        return host

    def request_stats(self) -> Dict[str, float]:
        """Per-request latency summaries (TTFT / TPOT / e2e, exact
        percentiles) over the retained request window."""
        from repro.obs import stats as obs_stats

        out: Dict[str, float] = {"requests": float(len(self._req_metrics))}
        for field in ("ttft_s", "tpot_s", "e2e_s"):
            s = obs_stats.latency_summary(
                [m[field] for m in self._req_metrics]
            )
            for k, v in s.items():
                out[f"{field[:-2]}_{k}"] = v
        return out

    def stats(self) -> Dict[str, float]:
        active = sorted(self.active_per_step)
        out = {
            "compiles": self.compiles,
            "traces": self.traces,
            "compile_s": self.compile_s,
            "num_buckets": self.num_buckets,
            "steps": self.steps,
            "tokens_generated": self.tokens_generated,
            "slot_occupancy": self.busy_slot_steps
            / max(1, self.steps * self.pool.max_slots),
            # Sustained concurrency: the in-flight request count per decode
            # step — median is the bench's density metric (robust to the
            # ramp-up/drain tails of a saturated run).
            "active_median": float(active[len(active) // 2]) if active else 0.0,
            "active_peak": float(active[-1]) if active else 0.0,
            "active_mean": float(sum(active)) / len(active) if active else 0.0,
            **self.request_stats(),
        }
        if self.pool.paged:
            out.update(
                pool_blocks_total=float(self.pool.total_blocks - 1),
                peak_blocks_used=float(self.peak_blocks_used),
                blocks_written=float(self.blocks_written),
            )
        return out

    # -- one-shot batch API (launch.serve.generate rides this) -------------

    def generate_batch(
        self,
        params,
        prompts,                  # (B, S) int32
        num_tokens: int,
        *,
        key: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Dict[str, float]]:
        """Serve a same-length batch as B independent requests with keys
        ``fold_in(key, i)``.  Per request, greedy output is token-identical
        to ``generate_reference(prompts[i:i+1], key=fold_in(key, i))`` —
        each request is its own DI stream, which is the multi-client
        semantics (the whole-generation engine instead draws one joint
        link mask across the batch)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        prompts = np.asarray(prompts, np.int32)
        b = prompts.shape[0]
        compiles_before, compile_s_before = self.compiles, self.compile_s
        reqs = [
            self.submit(prompts[i], num_tokens, key=jax.random.fold_in(key, i))
            for i in range(b)
        ]
        t0 = time.perf_counter()
        self.run(params)
        t_total = time.perf_counter() - t0
        compile_s = self.compile_s - compile_s_before
        exec_s = max(t_total - compile_s, 1e-9)
        tokens = jnp.asarray(np.stack([r.tokens for r in reqs]))
        timings = {
            "generate_s": exec_s,
            "decode_s_per_token": exec_s / max(1, num_tokens),
            "tokens_per_s": (b * num_tokens) / exec_s,
            "traces": float(self.traces),
            "compiles": float(self.compiles),
            "compile_s": compile_s,
            "compiled_this_call": float(self.compiles > compiles_before),
            "slot_occupancy": self.stats()["slot_occupancy"],
        }
        return tokens, timings


# ---------------------------------------------------------------------------
# Process-wide engine registry (mirrors serve.default_engine)
# ---------------------------------------------------------------------------

_ENGINES: Dict[Tuple, ContinuousEngine] = {}
_MAX_ENGINES = 4      # each engine retains a device slot pool; bound the set


def pool_engine(cfg: ModelConfig, pool: Optional[PoolConfig] = None) -> ContinuousEngine:
    """Engine per (cfg, pool) — the slot pool and its compiled programs
    survive across callers, which is the whole point.  The registry is a
    small LRU: every distinct cfg (each loss-rate/channel override bakes a
    new one) holds a full device slot pool, so e.g. a loss-rate sweep must
    not accumulate pools without bound.  An evicted engine keeps working
    for anyone still holding it; it just stops being shared."""
    pool = pool or PoolConfig()
    k = (cfg, pool)
    if k in _ENGINES:
        _ENGINES[k] = _ENGINES.pop(k)          # refresh LRU position
        return _ENGINES[k]
    while len(_ENGINES) >= _MAX_ENGINES:
        _ENGINES.pop(next(iter(_ENGINES)))
    _ENGINES[k] = ContinuousEngine(cfg, pool)
    return _ENGINES[k]


def engine_for(
    cfg: ModelConfig, prompt_len: int, num_tokens: int
) -> ContinuousEngine:
    """Engine whose pool covers (prompt_len, num_tokens), with both
    dimensions rounded to powers of two so repeated one-shot ``generate()``
    calls with nearby signatures coalesce onto one pool."""
    pool = PoolConfig(
        max_prompt=pow2_bucket(prompt_len),
        max_new=pow2_bucket(num_tokens, 16),
    )
    return pool_engine(cfg, pool)


def clear_engines() -> None:
    _ENGINES.clear()


# ---------------------------------------------------------------------------
# Simulator bridge: serve a sim batch through the live engine
# ---------------------------------------------------------------------------

def make_sim_server(
    engine: ContinuousEngine,
    params,
    *,
    prompt_lens: Sequence[int] = (8, 16, 32),
    num_tokens: int = 8,
    seed: int = 0,
    chaos=None,
    sla_for=None,
):
    """Adapter for ``net.simulator.run_sim(engine=...)``: maps each sim
    request (by rid, deterministically) to a synthetic prompt whose length
    cycles through ``prompt_lens`` (>= 3 buckets by default), serves the
    batch through the live engine, and returns the measured wall seconds —
    so the simulator's reported p50/p99 include real compute *and* real
    compile behavior (the first batch hitting a new bucket pays its AOT
    build, steady state pays none).

    ``chaos`` (a ``net.chaos.ChaosSchedule``) applies pool-level faults —
    the block squeeze — to the live engine at each batch's simulated start
    time (the simulator passes ``now=`` because ``serve_batch`` declares
    it).  ``sla_for`` maps a sim rid to an ``SLA`` when the engine has a
    scheduler attached (None = best-effort)."""
    vocab = engine.cfg.vocab_size
    base = jax.random.PRNGKey(seed)
    echaos = None
    if chaos:
        from repro.net.chaos import EngineChaos

        echaos = EngineChaos(engine, chaos)

    def serve_batch(reqs, now: float = 0.0) -> float:
        if echaos is not None:
            echaos.apply(now)
        t0 = time.perf_counter()
        for r in reqs:
            rid = int(r.rid)
            length = int(prompt_lens[rid % len(prompt_lens)])
            prompt = np.random.RandomState(seed + rid).randint(
                0, vocab, size=(length,)
            ).astype(np.int32)
            engine.submit(
                prompt, num_tokens, key=jax.random.fold_in(base, rid),
                sla=sla_for(rid) if sla_for is not None else None,
            )
        engine.run(params)
        return time.perf_counter() - t0

    return serve_batch
