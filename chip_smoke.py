#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: split-LM serving and COMtune
fine-tuning at qwen1.5-0.5b's published width (24 layers, d_model 1024,
16 heads, 16 KV heads, head dim 64, vocab 151936, bf16).  Weights and
prompts are random from ``--seed``; nothing is downloaded.

    python chip_smoke.py             # one chip: serve (contiguous bf16 and
                                     # paged int8 pools), kernel numerics, train
    python chip_smoke.py --chips 4   # four chips: the sharded router (one pool
                                     # per chip) against one engine on chip 0

Every phase runs in this one process (a chip belongs to one process) and
none catches its own failure: the script exits non-zero, printing no
result, when JAX finds no TPU, when a Pallas override would hide the
kernels, or when any check fails.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times and tokens/s printed on the way are single unrepeated smoke
figures, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.guards import no_recompile  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels.decode_attention import (  # noqa: E402
    decode_attention,
    paged_decode_attention,
)
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.steps import make_prefill_step, make_serve_step  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import cache as cache_lib, lm  # noqa: E402
from repro.serve import ContinuousEngine, PoolConfig, continuous, router  # noqa: E402
from repro.serve.continuous import pow2_bucket  # noqa: E402

ARCH = "qwen1.5-0.5b"
N_REQUESTS, PROMPT_LEN, NEW_TOKENS, LOSS_RATE = 8, 64, 32, 0.3
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 4, 8, 128
# The batch-1 reference loop and the 8-slot engine are different XLA
# programs, and on the chip their bf16 roundings differ.  Over 151936 bf16
# logits that flips the argmax wherever the top two are nearly tied, and
# the two greedy decodes then part.  So each request must match
# generate_reference token for token up to the first place they part, and
# there the engine's token must be a near-tie under the reference's own
# logits: within TIE_TOL of the reference's top logit, as a fraction of the
# top logit's lead over the mean logit.  A wrong head, mask, cache slot or
# link draw picks a token about as far below the top as the mean (ratio ~1).
TIE_TOL = 0.1
# Switches that would swap the decode kernel for interpret mode or the jnp
# reference; the smoke run must see the compiled kernel.
HIDING_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_FLASH_DECODE_IMPL")
# Kernel-vs-reference bound, as a fraction of max|V|.  Both paths compute
# in f32 from bf16 (or int8 x bf16-scale) inputs; they may differ in
# whether the MXU rounds an f32 operand (the dequantized K/V, the softmax
# weights P) to bf16, which costs up to 2^-9 relative per rounding, and
# the output is stored in bf16 (another 2^-9).  The output is a convex
# combination of V rows, so the difference is a few 2^-9 * max|V|; 16 *
# 2^-9 leaves ~5x headroom while a wrong head, block or mask is off by
# O(max|V|).
KERNEL_TOL = 16 * 2.0**-9


def log(msg: str) -> None:
    print(msg, flush=True)  # noqa: RPA006 — the smoke report is stdout by contract


@contextlib.contextmanager
def phase(name: str, seconds: dict):
    log(f"[{name}] start")
    t0 = time.perf_counter()
    yield
    seconds[name] = time.perf_counter() - t0
    log(f"[{name}] passed in {seconds[name]:.1f} s")


def assert_kernel_in(compiled, label: str) -> None:
    """The compiled program runs a Pallas kernel on the chip (not the jnp
    reference, not interpret mode)."""
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{label}: no tpu_custom_call in the compiled program"
    )


def check_engine(eng, label: str) -> None:
    assert_kernel_in(eng.decode_executable, f"{label} decode step")
    assert eng.compiles == eng.num_buckets + 1, (
        f"{label}: {eng.compiles} compiles for {eng.num_buckets} buckets"
    )
    log(f"  {label}: compiles={eng.compiles} (buckets={eng.num_buckets}), "
        f"compile {eng.compile_s:.1f} s")


def serve_twice(params, cfg, prompts, key, eng, label, *,
                pass_engine: bool):
    """Serve the batch through ``launch.serve.generate``, then serve it
    again under ``no_recompile``: the warm repeat must build nothing and
    give the same tokens.  Without ``pass_engine``, ``generate`` picks
    its default engine, which must be ``eng``."""
    engine = eng if pass_engine else None
    toks, _ = serve.generate(params, cfg, prompts, NEW_TOKENS, key=key,
                             engine=engine)
    with no_recompile(engines=(eng,)):
        again, timings = serve.generate(params, cfg, prompts, NEW_TOKENS,
                                        key=key, engine=engine)
    assert np.array_equal(np.asarray(toks), np.asarray(again)), (
        f"{label}: warm repeat changed the tokens"
    )
    log(f"  {label}: smoke tokens/s (warm repeat, one run, not a "
        f"benchmark) {timings['tokens_per_s']:.1f}")
    return np.asarray(toks)


def serve_contiguous(params, cfg, prompts, key):
    eng = continuous.engine_for(cfg, PROMPT_LEN, NEW_TOKENS)
    toks = serve_twice(params, cfg, prompts, key, eng, "contiguous",
                       pass_engine=False)
    check_engine(eng, "contiguous")
    return toks


def reference_identity(params, cfg, prompts, key, toks):
    """Each request's engine tokens against ``generate_reference`` for that
    request alone under its own key ``fold_in(key, i)``: identical up to
    the first token where they part, and a near-tie there (TIE_TOL)."""
    prefill = jax.jit(make_prefill_step(cfg))
    step = jax.jit(make_serve_step(cfg))

    def logits_after(prompt, fed, k):
        """generate_reference's loop for one request, fed the tokens
        ``fed``: the logits it picks the next token from."""
        cache = cache_lib.init_cache(cfg, 1, PROMPT_LEN + NEW_TOKENS)
        k, sub = jax.random.split(k)
        logits, cache = prefill(params, {"tokens": prompt}, cache, sub)
        for t, token in enumerate(fed):
            k, sub = jax.random.split(k)
            logits, cache = step(params, jnp.full((1, 1), token, jnp.int32),
                                 cache, jnp.int32(PROMPT_LEN + t), sub)
        return np.asarray(logits[0], np.float32)

    parted = {}
    for i in range(N_REQUESTS):
        k = jax.random.fold_in(key, i)
        ref, _ = serve.generate_reference(params, cfg, prompts[i : i + 1],
                                          NEW_TOKENS, key=k)
        diff = np.flatnonzero(np.asarray(ref[0]) != toks[i])
        if not diff.size:
            continue
        n = int(diff[0])
        logits = logits_after(prompts[i : i + 1], toks[i, :n], k)
        assert int(np.argmax(logits)) == int(ref[0, n]), (
            f"request {i}: the fed loop does not reproduce generate_reference"
        )
        top = float(logits.max())
        ratio = (top - float(logits[toks[i, n]])) / (top - float(logits.mean()))
        parted[i] = (n, ratio)
        assert ratio <= TIE_TOL, (
            f"request {i}: engine token {n} is {ratio:.3g} of the lead below "
            f"generate_reference's top logit (near-tie bound {TIE_TOL})"
        )
    log(f"  {N_REQUESTS - len(parted)} of {N_REQUESTS} requests identical to "
        f"generate_reference; the rest part at a near-tie, "
        f"{{request: (token, gap / lead)}}: "
        + str({i: (n, float(f"{r:.3g}")) for i, (n, r) in parted.items()}))


def serve_paged_int8(params, cfg, prompts, key):
    eng = ContinuousEngine(
        cfg.with_updates(kv_cache_dtype="int8"),
        PoolConfig(max_prompt=pow2_bucket(PROMPT_LEN),
                   max_new=pow2_bucket(NEW_TOKENS, 16), paged=True),
    )
    serve_twice(params, eng.cfg, prompts, key, eng, "paged int8",
                pass_engine=True)
    check_engine(eng, "paged int8")


def kernel_numerics(cfg, key):
    """decode_attention / paged_decode_attention, kernel vs ref, at the
    config's head layout with bf16 and int8 caches and ragged lengths."""
    b, c, bs = 8, 192, 16
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // kvh
    n_valid = jnp.array([1, 15, 33, 64, 100, 127, 150, 191], jnp.int32)
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (b, 1, kvh, g, hd), jnp.bfloat16)
    caches = {
        "bf16": {
            "k": jax.random.normal(ks[1], (b, c, kvh, hd), jnp.bfloat16),
            "v": jax.random.normal(ks[2], (b, c, kvh, hd), jnp.bfloat16),
        },
        "int8": {
            "k": jax.random.randint(ks[1], (b, c, kvh, hd), -127, 128, jnp.int8),
            "v": jax.random.randint(ks[2], (b, c, kvh, hd), -127, 128, jnp.int8),
            "k_scale": (jax.random.uniform(ks[3], (b, c, kvh)) * 0.05 + 0.01
                        ).astype(jnp.bfloat16),
            "v_scale": (jax.random.uniform(ks[4], (b, c, kvh)) * 0.05 + 0.01
                        ).astype(jnp.bfloat16),
        },
    }
    j = c // bs
    table = (jax.random.permutation(ks[5], b * j) + 1).reshape(b, j)

    def to_pool(cache):
        """Scatter each request's rows into pool blocks per the table."""
        pool = {}
        for name, a in cache.items():
            blocks = a.reshape((b * j, bs) + a.shape[2:])
            empty = jnp.zeros((b * j + 1,) + blocks.shape[1:], a.dtype)
            pool[name] = empty.at[table.reshape(-1)].set(blocks)
        return pool

    def contiguous(impl, q, cache, n):
        return decode_attention(q, cache, n, impl=impl)

    def slot_vmapped(impl, q, cache, n):
        """The slot-pool engine's form: batch-1 calls vmapped over slots."""
        one = lambda q, cache, n: decode_attention(
            q[None], {k: a[None] for k, a in cache.items()}, n, impl=impl
        )[0]
        return jax.vmap(one)(q, cache, n)

    def paged(impl, q, pool, n):
        return paged_decode_attention(q, pool, table, n, seq_len=c,
                                      block_size=bs, impl=impl)

    for dtype, cache in caches.items():
        v = cache["v"].astype(jnp.float32)
        if "v_scale" in cache:
            v = v * cache["v_scale"].astype(jnp.float32)[..., None]
        bound = KERNEL_TOL * float(jnp.max(jnp.abs(v)))
        compare_kernel_to_ref(f"contiguous {dtype}", contiguous,
                              (q, cache, n_valid), bound)
        compare_kernel_to_ref(f"slot-vmapped {dtype}", slot_vmapped,
                              (q, cache, n_valid), bound)
        compare_kernel_to_ref(f"paged {dtype}", paged,
                              (q, to_pool(cache), n_valid), bound)


def compare_kernel_to_ref(label, fn, args, bound):
    """``fn(impl, *args)`` with the compiled kernel against the reference."""
    compiled = jax.jit(functools.partial(fn, "kernel")).lower(*args).compile()
    assert_kernel_in(compiled, f"{label} kernel")
    got = np.asarray(compiled(*args), np.float32)
    want = np.asarray(jax.jit(functools.partial(fn, "ref"))(*args), np.float32)
    err = float(np.max(np.abs(got - want)))
    assert np.all(np.isfinite(got)) and err <= bound, (
        f"{label}: max |kernel - ref| {err:.3g} > {bound:.3g}"
    )
    log(f"  {label}: max |kernel - ref| = {err:.3g} (bound {bound:.3g})")


def train_steps(seed: int):
    _, losses, _ = train(
        ARCH, steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
        full_size=True, train_link="dropout", seed=seed,
        log_every=TRAIN_STEPS,
    )
    assert len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)), losses
    log(f"  losses {losses}")


def sharded_vs_one_chip(params, cfg, prompts, key, chips: int):
    """One slot-pool shard per chip behind the router, against one
    ContinuousEngine on chip 0 serving the same requests."""
    one, _ = serve.generate(params, cfg, prompts, NEW_TOKENS, key=key)
    check_engine(continuous.engine_for(cfg, PROMPT_LEN, NEW_TOKENS), "chip 0")
    fleet, timings = serve.generate(params, cfg, prompts, NEW_TOKENS, key=key,
                                    num_shards=chips)
    log(f"  sharded smoke tokens/s (cold run, one run, not a benchmark) "
        f"{timings['tokens_per_s']:.1f}")
    assert np.array_equal(np.asarray(one), np.asarray(fleet)), (
        "sharded tokens differ from the one-chip engine"
    )
    eng = router.sharded_engine(
        cfg, PoolConfig(max_prompt=pow2_bucket(PROMPT_LEN),
                        max_new=pow2_bucket(NEW_TOKENS, 16)),
        num_shards=chips,
    )
    for i, shard in enumerate(eng.shards):
        check_engine(shard, f"shard {i}")
        assert shard.devices_in_use() == {shard.device}, (
            f"shard {i}: state/programs on {shard.devices_in_use()}, "
            f"expected {shard.device}"
        )
    placed = [str(s.device) for s in eng.shards]
    assert len(set(placed)) == chips, placed
    log(f"  shards on {placed}; tokens identical to the one-chip arm")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    hidden = [v for v in HIDING_ENV if os.environ.get(v)]
    if hidden:
        raise SystemExit(f"chip_smoke: unset {hidden}: they hide the kernels")

    cache_dir = setup_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {dev.platform}")
    if len(devices) < args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips}, but {len(devices)} visible"
        )
    log(f"device: {dev.device_kind} x{len(devices)}; compile cache: {cache_dir}")

    cfg = get_config(ARCH)
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=LOSS_RATE, channel="ge")
    )
    key = jax.random.PRNGKey(args.seed)
    pkey, rkey, kkey = jax.random.split(key, 3)
    params = lm.init_lm(pkey, cfg)
    prompts = jax.random.randint(
        rkey, (N_REQUESTS, PROMPT_LEN), 0, cfg.vocab_size, jnp.int32
    )
    seconds: dict = {}
    if args.chips == 1:
        with phase("serve contiguous bf16", seconds):
            toks = serve_contiguous(params, cfg, prompts, key)
        with phase("serve paged int8", seconds):
            serve_paged_int8(params, cfg, prompts, key)
        with phase("kernel numerics", seconds):
            kernel_numerics(cfg, kkey)
        with phase("train", seconds):
            train_steps(args.seed)
        with phase("reference identity", seconds):
            reference_identity(params, cfg, prompts, key, toks)
    else:
        with phase(f"sharded serve x{args.chips}", seconds):
            sharded_vs_one_chip(params, cfg, prompts, key, args.chips)
    log("phase seconds (compile included; one run): "
        + ", ".join(f"{k} {v:.1f}" for k, v in seconds.items()))
    print(json.dumps({"ok": True, "device": {  # noqa: RPA006 — stdout contract
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
