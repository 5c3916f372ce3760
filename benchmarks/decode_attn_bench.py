"""Decode-attention step-cost benchmark: full-cache naive vs length-masked
flash decode.

Two views, emitted to ``BENCH_decode_attn.json`` (the CI artifact):

* **micro** — one attention layer's decode step at fixed ``max_seq``,
  sweeping the valid length: the legacy path (full-cache dequantize +
  masked naive softmax, exactly what ``attn_impl="naive"`` runs under jit)
  against ``repro.kernels.decode_attention`` (O(valid) blocks, inline int8
  dequant), for int8 and model-dtype caches.  ``n_valid`` rides as a
  traced argument so XLA cannot constant-fold the mask.  Each row also
  reports the analytic bytes touched (``models.cache.decode_read_bytes``
  semantics at layer scope).
* **engine** — tokens/s of the continuous-batching slot pool on a
  mixed-length workload with ``attn_impl="naive"`` vs ``"flash_decode"``
  (same params, same keys; outputs are compared for drift).

CI smoke asserts the masked path beats the full-cache path by
``--assert-min-speedup`` (default gate 2x) at every swept valid length
<= max_seq/8 on the int8 cache — the acceptance bar for "decode cost
scales with valid tokens, not max_seq".

    PYTHONPATH=src python -m benchmarks.decode_attn_bench \
        [--max-seq 1024] [--assert-min-speedup 2.0] \
        [--out BENCH_decode_attn.json]
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ARCHITECTURES
from repro.kernels.decode_attention import decode_attention, decode_block_kv
from repro.launch.compile_cache import setup_compile_cache
from repro.models import cache as cache_lib, lm
from repro.models.attention import _naive_attn, _read_cache
from repro.serve import ContinuousEngine, PoolConfig

logger = obs.get_logger("decode_attn_bench")


def _full_cache_step(q, cache, n_valid, softcap=0.0):
    """The legacy decode attention: dequantize the WHOLE cache, mask, softmax
    over all max_seq positions (what attn_impl="naive" compiles under jit)."""
    k, v = _read_cache(cache, q.dtype)
    c = k.shape[1]
    mask = (jnp.arange(c)[None, :] < n_valid)[:, None, None, None, :]
    return _naive_attn(q, k, v, mask, softcap)


def _make_cache(key, b, c, kvh, hd, kv_dtype, dtype):
    ks = jax.random.split(key, 4)
    if kv_dtype == "int8":
        return {
            "k": jax.random.randint(ks[0], (b, c, kvh, hd), -127, 128, jnp.int8),
            "v": jax.random.randint(ks[1], (b, c, kvh, hd), -127, 128, jnp.int8),
            "k_scale": (jax.random.uniform(ks[2], (b, c, kvh)) * 0.05 + 0.01
                        ).astype(jnp.bfloat16),
            "v_scale": (jax.random.uniform(ks[3], (b, c, kvh)) * 0.05 + 0.01
                        ).astype(jnp.bfloat16),
        }
    return {
        "k": jax.random.normal(ks[0], (b, c, kvh, hd), dtype),
        "v": jax.random.normal(ks[1], (b, c, kvh, hd), dtype),
    }


def _time_step(fn, args, reps: int, rounds: int) -> float:
    """Median wall seconds of one call (blocked), over ``rounds`` batches
    of ``reps`` back-to-back dispatches."""
    jax.block_until_ready(fn(*args))      # warm (compile)
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps)
    return float(np.median(times))


def _layer_read_bytes(c, kvh, hd, kv_dtype, valid, block_kv, masked):
    itemsize = 1 if kv_dtype == "int8" else 4
    if masked:
        bkv = decode_block_kv(c, block_kv)
        rows = min(-(-min(valid, c) // bkv) * bkv, c)
    else:
        rows = c
    row = 2 * kvh * hd * itemsize + (2 * kvh * 2 if kv_dtype == "int8" else 0)
    return rows * row


def micro_bench(
    max_seq: int, valids, b: int, kvh: int, groups: int, hd: int,
    block_kv: int, reps: int, rounds: int,
) -> dict:
    out = {}
    for kv_dtype in ("int8", "f32"):
        dtype = jnp.float32
        q = jax.random.normal(
            jax.random.PRNGKey(0), (b, 1, kvh, groups, hd), dtype
        )
        cache = _make_cache(
            jax.random.PRNGKey(1), b, max_seq, kvh, hd, kv_dtype, dtype
        )
        old_fn = jax.jit(_full_cache_step)  # noqa: RPA001 — one deliberate compile per kv_dtype config
        new_fn = jax.jit(  # noqa: RPA001 — one deliberate compile per kv_dtype config
            functools.partial(decode_attention, block_kv=block_kv)
        )
        rows = []
        for v in valids:
            n = jnp.int32(v)
            t_old = _time_step(old_fn, (q, cache, n), reps, rounds)
            t_new = _time_step(new_fn, (q, cache, n), reps, rounds)
            rows.append({
                "valid": int(v),
                "old_ms": t_old * 1e3,
                "masked_ms": t_new * 1e3,
                "speedup": t_old / max(t_new, 1e-12),
                "read_bytes_old": _layer_read_bytes(
                    max_seq, kvh, hd, kv_dtype, v, block_kv, masked=False),
                "read_bytes_masked": _layer_read_bytes(
                    max_seq, kvh, hd, kv_dtype, v, block_kv, masked=True),
            })
        out[kv_dtype] = {
            "max_seq": max_seq, "batch": b, "kv_heads": kvh,
            "groups": groups, "head_dim": hd, "block_kv": block_kv,
            "rows": rows,
        }
    return out


def engine_bench(tokens: int = 12, n_requests: int = 8) -> dict:
    """Slot-pool tokens/s, naive vs flash_decode, identical greedy output."""
    import dataclasses

    base = ARCHITECTURES["qwen1.5-0.5b"].reduced(kv_cache_dtype="int8")
    base = base.with_updates(
        link=dataclasses.replace(base.link, loss_rate=0.1, channel="iid")
    )
    params = lm.init_lm(jax.random.PRNGKey(0), base)
    lengths = [4 + (3 * i) % 24 for i in range(n_requests)]
    prompts = [
        np.asarray(jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(7), i), (L,), 0,
            base.vocab_size, jnp.int32,
        ))
        for i, L in enumerate(lengths)
    ]
    results = {}
    outputs = {}
    for impl in ("naive", "flash_decode"):
        eng = ContinuousEngine(
            base,
            PoolConfig(max_slots=4, max_new=tokens, max_prompt=32, min_bucket=8),
            attn_impl=impl,
        )
        key = jax.random.PRNGKey(3)

        def serve():
            reqs = [
                eng.submit(p, tokens, key=jax.random.fold_in(key, i))
                for i, p in enumerate(prompts)
            ]
            t0 = time.perf_counter()
            eng.run(params)
            return time.perf_counter() - t0, reqs

        serve()                                   # warm: AOT builds
        wall, reqs = serve()
        outputs[impl] = np.stack([r.tokens for r in reqs])
        results[impl] = {
            "tokens_per_s": n_requests * tokens / wall,
            "wall_s": wall,
            "compiles": eng.compiles,
        }
    results["outputs_identical"] = bool(
        (outputs["naive"] == outputs["flash_decode"]).all()
    )
    results["speedup"] = (
        results["flash_decode"]["tokens_per_s"]
        / max(results["naive"]["tokens_per_s"], 1e-9)
    )
    results["pool_max_seq"] = 32 + tokens
    return results


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--valids", default="16,64,128,256,512,1024",
                    help="comma-separated valid lengths to sweep")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--block-kv", type=int, default=64)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--no-engine", action="store_true",
                    help="skip the slot-pool engine comparison")
    ap.add_argument("--out", default="BENCH_decode_attn.json")
    ap.add_argument(
        "--assert-min-speedup", type=float, default=None,
        help="fail unless masked/full speedup >= this at every int8 sweep "
        "point with valid <= max_seq/8",
    )
    args = ap.parse_args()

    valids = [int(v) for v in args.valids.split(",") if v]
    micro = micro_bench(
        args.max_seq, valids, args.batch, args.kv_heads, args.groups,
        args.head_dim, args.block_kv, args.reps, args.rounds,
    )
    qwen8 = ARCHITECTURES["qwen1.5-0.5b"].with_updates(kv_cache_dtype="int8")
    result = {
        "bench": "decode_attn",
        "backend": jax.default_backend(),
        "micro": micro,
        "model_read_bytes_example": {
            "arch": "qwen1.5-0.5b+int8", "max_seq": 1024, "valid": 128,
            "full": cache_lib.decode_read_bytes(qwen8, 1024, 128, masked=False),
            "masked": cache_lib.decode_read_bytes(qwen8, 1024, 128, masked=True),
        },
    }
    if not args.no_engine:
        result["engine"] = engine_bench()

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)

    for kv_dtype, m in micro.items():
        logger.info(f"[{kv_dtype} cache, max_seq={m['max_seq']}]")
        for r in m["rows"]:
            logger.info(
                f"  valid={r['valid']:>5}: full {r['old_ms']:7.3f} ms | "
                f"masked {r['masked_ms']:7.3f} ms | {r['speedup']:5.2f}x | "
                f"bytes {r['read_bytes_old']:>9} -> {r['read_bytes_masked']:>9}"
            )
    if "engine" in result:
        e = result["engine"]
        logger.info(
            f"[slot pool, int8] naive {e['naive']['tokens_per_s']:.1f} tok/s"
            f" | flash_decode {e['flash_decode']['tokens_per_s']:.1f} tok/s"
            f" | {e['speedup']:.2f}x | identical={e['outputs_identical']}"
        )
    logger.info(f"-> {args.out}")

    ok = True
    if args.assert_min_speedup is not None:
        gate = [r for r in micro["int8"]["rows"]
                if r["valid"] * 8 <= args.max_seq]
        if not gate:
            logger.error("ASSERT FAILED: no sweep point with valid <= max_seq/8")
            ok = False
        for r in gate:
            if r["speedup"] < args.assert_min_speedup:
                logger.info(
                    f"ASSERT FAILED: int8 valid={r['valid']} speedup "
                    f"{r['speedup']:.2f}x < {args.assert_min_speedup}x"
                )
                ok = False
    if "engine" in result and not result["engine"]["outputs_identical"]:
        logger.error("ASSERT FAILED: naive vs flash_decode engine outputs differ")
        ok = False
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
