"""Decode throughput benchmark: seed per-token loop vs scan-compiled engine.

Times repeated ``generate()`` calls through the ``repro.serve`` engine
(one jitted ``lax.scan`` program per signature, compile-cached) against the
seed per-token Python loop (``generate_reference``, one jit dispatch per
token), and emits ``BENCH_decode.json`` with tokens/s, per-call p50/p99,
and the engine's trace count — the perf-trajectory artifact CI uploads.

    PYTHONPATH=src python -m benchmarks.decode_bench \
        [--arch qwen1.5-0.5b] [--iters 5] [--out BENCH_decode.json] \
        [--assert-min-tokens-per-s 1.0] [--assert-single-trace]
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ARCHITECTURES, get_config
from repro.launch.serve import generate_reference
from repro.launch.compile_cache import setup_compile_cache
from repro.models import cache as cache_lib, lm
from repro.obs.stats import latency_summary
from repro.serve import DecodeEngine

logger = obs.get_logger("decode_bench")


def run_bench(
    arch: str = "qwen1.5-0.5b",
    batch: int = 4,
    prompt_len: int = 16,
    tokens: int = 32,
    iters: int = 5,
    loss_rate: float = 0.1,
    channel: str = "iid",
    full_size: bool = False,
    reference_iters: int = 2,
) -> dict:
    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    import dataclasses

    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=loss_rate, channel=channel)
    )
    key = jax.random.PRNGKey(0)
    params = lm.init_lm(key, cfg)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size, jnp.int32
    )

    engine = DecodeEngine()
    # First call warms up internally (trace + compile, reported as
    # compile_s) and then times a pure execution, like every later call.
    call_times = []
    compile_s = 0.0
    for i in range(iters):
        _, t = engine.generate(
            params, cfg, prompts, tokens, key=jax.random.PRNGKey(i)
        )
        call_times.append(t["generate_s"])
        compile_s += t["compile_s"]
    stats = engine.stats()
    eng_stats = {
        "tokens_per_s": batch * tokens / float(np.median(call_times)),
        "compile_s": compile_s,
        "traces": stats["traces"],
        "calls": stats["calls"],
        **latency_summary(call_times),
    }

    # Like-for-like with the engine: whole-call time (prefill + decode).
    ref_times = []
    for i in range(max(reference_iters, 1)):
        _, t = generate_reference(
            params, cfg, prompts, tokens, key=jax.random.PRNGKey(i)
        )
        ref_times.append(t["prefill_s"] + t["decode_s_per_token"] * tokens)
    ref_stats = {
        "tokens_per_s": batch * tokens / float(np.median(ref_times)),
        **latency_summary(ref_times),
    }

    return {
        "bench": "decode",
        "arch": arch,
        "batch": batch,
        "prompt_len": prompt_len,
        "tokens": tokens,
        "iters": iters,
        "loss_rate": loss_rate,
        "channel": channel,
        "full_size": full_size,
        "cache_bytes": cache_lib.cache_bytes(cfg, batch, prompt_len + tokens),
        "backend": jax.default_backend(),
        "engine": eng_stats,
        "reference": ref_stats,
        "speedup": eng_stats["tokens_per_s"] / max(ref_stats["tokens_per_s"], 1e-9),
        # With REPRO_OBS=1 the engine's registry-side metrics ride along.
        "obs": (
            obs.registry().histogram("decode_engine.generate_s").summary()
            if obs.registry().enabled else None
        ),
    }


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHITECTURES))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--reference-iters", type=int, default=2)
    ap.add_argument("--loss-rate", type=float, default=0.1)
    ap.add_argument(
        "--channel", default="iid",
        choices=["iid", "ge", "gilbert_elliott", "fading"],
    )
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--out", default="BENCH_decode.json")
    ap.add_argument(
        "--assert-min-tokens-per-s", type=float, default=None,
        help="fail (exit 1) if engine tokens/s is below this",
    )
    ap.add_argument(
        "--assert-single-trace", action="store_true",
        help="fail if the engine traced more than once across all calls",
    )
    args = ap.parse_args()

    result = run_bench(
        arch=args.arch,
        batch=args.batch,
        prompt_len=args.prompt_len,
        tokens=args.tokens,
        iters=args.iters,
        loss_rate=args.loss_rate,
        channel=args.channel,
        full_size=args.full_size,
        reference_iters=args.reference_iters,
    )
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    eng, ref = result["engine"], result["reference"]
    logger.info(
        f"decode_bench[{args.arch} b={args.batch} s={args.prompt_len}"
        f"+{args.tokens}]: engine {eng['tokens_per_s']:.1f} tok/s "
        f"(p50 {eng['p50_s']*1e3:.1f} ms, p99 {eng['p99_s']*1e3:.1f} ms, "
        f"traces={eng['traces']}/{eng['calls']} calls) | "
        f"reference {ref['tokens_per_s']:.1f} tok/s | "
        f"speedup {result['speedup']:.1f}x -> {args.out}"
    )

    ok = True
    if args.assert_min_tokens_per_s is not None:
        if eng["tokens_per_s"] < args.assert_min_tokens_per_s:
            logger.error(
                f"ASSERT FAILED: {eng['tokens_per_s']:.2f} tok/s < "
                f"{args.assert_min_tokens_per_s}"
            )
            ok = False
    if args.assert_single_trace and eng["traces"] != 1:
        logger.error(f"ASSERT FAILED: engine traced {eng['traces']} times (want 1)")
        ok = False
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
