"""Serving driver: batched distributed-inference (split LM) over the
emulated lossy IoT link — the paper's DI round (Eq. 12) generalized to
autoregressive decoding.

``generate()`` rides the continuous-batching slot-pool engine
(``repro.serve.continuous``) by default: the batch is served as B
independent requests (per-request RNG chains ``fold_in(key, i)``, bucketed
prefill, one fused decode step over the slot pool), so each request's
greedy output is token-identical to ``generate_reference(prompts[i:i+1],
key=fold_in(key, i))`` and repeated calls with nearby signatures reuse one
pool with zero steady-state recompiles.  Passing ``engine=DecodeEngine()``
(or ``greedy=False``) selects the whole-generation scan engine — one AOT
program per exact signature, which draws ONE joint link mask across the
batch (the legacy batch semantics its equivalence tests pin down).
``generate_reference()`` keeps the seed per-token Python loop (one jit
dispatch per token) as the equivalence oracle and benchmark baseline; all
paths report per-round message sizes and the analytic communication
latency of the unreliable protocol (paper §III-B), and time *compute* —
the timed regions end in ``jax.block_until_ready``, not async dispatch.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHITECTURES, get_config
from repro.core import ChannelConfig, comtune
from repro.core.compression import Compressor, PCASpec, QuantSpec
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import cache as cache_lib, lm
from repro.obs import get_logger
from repro.serve import default_engine


def _override_link(cfg, loss_rate=None, channel=None):
    if loss_rate is None and channel is None:
        return cfg
    import dataclasses

    updates = {}
    if loss_rate is not None:
        updates["loss_rate"] = loss_rate
    if channel is not None:
        updates["channel"] = channel
    return cfg.with_updates(link=dataclasses.replace(cfg.link, **updates))


def _link_accounting(cfg, batch: int) -> dict:
    """Per-round message size + analytic link latency (paper §III-B)."""
    channel_cfg = ChannelConfig(loss_rate=cfg.link.loss_rate)
    spec = comtune.LinkSpec(
        loss_rate=cfg.link.loss_rate,
        compressor=_accounting_compressor(cfg),
        channel=cfg.link.channel,
        channel_params=tuple(cfg.link.channel_params),
        fec_k=cfg.link.fec_k,
        fec_m=cfg.link.fec_m,
        fec_kind=cfg.link.fec_kind,
    )
    return {
        "link_latency_s_per_round": comtune.di_latency_s(
            spec, cfg.d_model, batch, channel_cfg
        ),
        "message_kb_per_token": comtune.message_bytes(spec, cfg.d_model)
        * batch / 1e3,
    }


def generate(
    params,
    cfg,
    prompts: jax.Array,            # (B, S_prompt) int32
    num_tokens: int,
    loss_rate: float | None = None,
    key=None,
    greedy: bool = True,
    channel: str | None = None,
    temperature: float = 1.0,
    engine=None,
    num_shards: int = 0,
):
    """Returns (generated (B, num_tokens), timings dict).

    Default (``engine=None``, greedy): the continuous-batching slot-pool
    engine — per request ``i``, greedy output is token-for-token identical
    to ``generate_reference(prompts[i:i+1], key=fold_in(key, i))``, and
    the pool's AOT programs make repeated calls compile nothing new
    (``timings['compiles']``/``timings['traces']``).  ``num_shards > 1``
    rides the sharded router instead (``repro.serve.router``): one slot
    pool per device with occupancy-aware placement — same per-request
    token-identity contract, aggregate throughput scales with devices.
    With an explicit ``DecodeEngine`` (or sampling), the whole-generation
    scan engine serves the batch under its legacy joint-mask semantics,
    token-exact against ``generate_reference`` at the same batch under
    the same key.
    """
    cfg = _override_link(cfg, loss_rate=loss_rate, channel=channel)
    from repro.serve import ContinuousEngine, ShardedEngine, continuous
    from repro.serve import router as router_lib
    from repro.serve.continuous import PoolConfig, pow2_bucket

    if engine is None and greedy and not cfg.frontend and num_shards > 1:
        engine = router_lib.sharded_engine(
            cfg,
            PoolConfig(
                max_prompt=pow2_bucket(prompts.shape[1]),
                max_new=pow2_bucket(num_tokens, 16),
            ),
            num_shards=num_shards,
        )
    if engine is None and greedy and not cfg.frontend:
        # Frontend (VLM/audio) configs need an extra embed input the slot
        # pool doesn't carry yet — they stay on the whole-generation engine.
        engine = continuous.engine_for(cfg, prompts.shape[1], num_tokens)
    if isinstance(engine, (ContinuousEngine, ShardedEngine)):
        tokens, timings = engine.generate_batch(
            params, prompts, num_tokens,
            key=key if key is not None else jax.random.PRNGKey(0),
        )
    else:
        engine = engine or default_engine()
        tokens, timings = engine.generate(
            params, cfg, prompts, num_tokens,
            key=key, greedy=greedy, temperature=temperature,
        )
    timings.update(_link_accounting(cfg, prompts.shape[0]))
    return tokens, timings


def generate_reference(
    params,
    cfg,
    prompts: jax.Array,            # (B, S_prompt) int32
    num_tokens: int,
    loss_rate: float | None = None,
    key=None,
    greedy: bool = True,
    channel: str | None = None,
):
    """The seed per-token serving loop (one jit dispatch per token).

    Kept as the scan engine's equivalence oracle and the decode-bench
    baseline.  Unlike the seed, the timed regions block on the result:
    ``prefill_s`` / ``decode_s_per_token`` measure compute, not async
    dispatch.
    """
    assert greedy, "the reference loop is the greedy-equivalence oracle"
    key = key if key is not None else jax.random.PRNGKey(0)
    b, s_prompt = prompts.shape
    max_seq = s_prompt + num_tokens
    cfg = _override_link(cfg, loss_rate=loss_rate, channel=channel)
    prefill = jax.jit(make_prefill_step(cfg))
    step = jax.jit(make_serve_step(cfg))

    cache = cache_lib.init_cache(cfg, b, max_seq)
    key, sub = jax.random.split(key)
    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": prompts}, cache, sub)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    out = []
    token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    for i in range(num_tokens):
        out.append(token)
        key, sub = jax.random.split(key)
        logits, cache = step(params, token, cache, jnp.int32(s_prompt + i), sub)
        token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    jax.block_until_ready(token)
    t_decode = time.perf_counter() - t0

    timings = {
        "prefill_s": t_prefill,
        "decode_s_per_token": t_decode / max(1, num_tokens),
        "tokens_per_s": (b * num_tokens) / max(t_decode, 1e-9),
    }
    timings.update(_link_accounting(cfg, b))
    return jnp.concatenate(out, axis=1), timings


def _accounting_compressor(cfg) -> Compressor:
    """Compressor reflecting the configured scheme's true message size.

    PCA transmits ``pca_dim`` float32 coefficients per vector (Eq. 18), NOT
    the full d_model — mapping it to "identity" (as this function once did)
    over-reported PCA's message size by d_model/pca_dim x.
    """
    link = cfg.link
    if link.compression == "quant":
        return Compressor(
            kind="quant",
            quant=QuantSpec(
                bits=link.quant_bits,
                s_min=jnp.zeros(()), s_max=jnp.ones(()),
            ),
        )
    if link.compression == "pca":
        pca_dim = link.pca_dim or cfg.d_model // 4
        return Compressor(
            kind="pca",
            pca=PCASpec(
                w=jnp.zeros((pca_dim, cfg.d_model)),
                b=jnp.zeros((cfg.d_model,)),
            ),
        )
    return Compressor(kind="identity")


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHITECTURES), required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--loss-rate", type=float, default=0.1)
    ap.add_argument(
        "--channel", default="iid",
        choices=["iid", "ge", "gilbert_elliott", "fading"],
        help="serve-time channel process (repro.net.channels)",
    )
    ap.add_argument(
        "--protocol", default="unreliable",
        choices=["unreliable", "arq", "fec_arq"],
        help="report link latency under this repro.net protocol policy",
    )
    ap.add_argument(
        "--deadline", type=float, default=None,
        help="report P(the protocol delivers the full uplink within this "
        "many seconds) from the analytic completion PMFs — the same "
        "deadline_feasible oracle the SLA scheduler sheds against",
    )
    ap.add_argument(
        "--attn-impl", default=None,
        choices=["naive", "blockwise", "flash_decode"],
        help="override cfg.attn_impl — blockwise/flash_decode decode via the "
        "length-masked flash-decode kernel (O(valid) cache blocks/step), "
        "naive keeps the full-cache oracle",
    )
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument(
        "--num-shards", type=int, default=0,
        help="serve through the sharded router with this many per-device "
        "slot-pool shards (0/1 = single engine); shards wrap around the "
        "visible devices — force more with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N",
    )
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    if args.attn_impl:
        cfg = cfg.with_updates(attn_impl=args.attn_impl)
    key = jax.random.PRNGKey(0)
    params = lm.init_lm(key, cfg)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32
    )
    toks, timings = generate(
        params, cfg, prompts, args.tokens, loss_rate=args.loss_rate, key=key,
        channel=args.channel, num_shards=args.num_shards,
    )
    log = get_logger("repro.launch.serve")
    log.info(f"generated: {np.asarray(toks)[:, :10]} ...")
    for k, v in timings.items():
        log.info(f"{k}: {v:.5f}")

    # Per-round latency PMF under the selected protocol policy (repro.net),
    # at the selected channel's stationary loss rate (which for "fading" is
    # set by its distance parameters, not --loss-rate).
    from repro.net import make_protocol
    from repro.net.protocol import latency_quantile

    channel_cfg = ChannelConfig(loss_rate=args.loss_rate)
    spec = comtune.LinkSpec(
        loss_rate=args.loss_rate,
        compressor=_accounting_compressor(cfg),
        channel=args.channel,
    )
    p_eff = spec.resolve_channel().stationary_loss_rate
    n_t = channel_cfg.num_packets_for_bytes(
        comtune.message_bytes(spec, cfg.d_model) * args.batch
    )
    proto = make_protocol(args.protocol)
    lat, pmf = proto.latency_pmf(n_t, channel_cfg, loss_rate=p_eff)
    mean_lat = float(np.dot(lat, pmf))
    p99 = latency_quantile(lat, pmf, 0.99)
    log.info(
        f"protocol={proto.name} E[link_latency_s]: {mean_lat:.5f} p99: {p99:.5f}"
    )
    if args.deadline is not None:
        from repro.net import deadline_feasible

        p_meet = deadline_feasible(
            proto, n_t, channel_cfg, args.deadline, loss_rate=p_eff
        )
        log.info(
            f"P(uplink complete within {args.deadline:g}s): {p_meet:.4f}"
        )


if __name__ == "__main__":
    main()
