"""Causal LM assembled from the unified stack, with the COMtune link layer
as a first-class feature (paper Eq. 8 for training, Eq. 12 for serving).

The link sits between the device-side and server-side unit scans; its
compression parameters (quantization scale factors / PCA basis) live inside
the parameter pytree so calibration results are part of checkpoints and the
lowered multi-pod program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import comtune
from repro.core.compression import Compressor, PCASpec, QuantSpec
from repro.models import frontends, rope as rope_lib, transformer
from repro.models.common import (
    Params,
    apply_norm,
    dense_init,
    dtype_of,
    embed_init,
    init_norm,
    split_keys,
)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_link_params(key, cfg: ModelConfig, dtype) -> Params:
    """Compression parameters at the split point (calibrated later)."""
    d = cfg.d_model
    link = cfg.link
    p: Params = {}
    if link.compression == "quant":
        p["s_min"] = jnp.full((d,), -6.0, jnp.float32)
        p["s_max"] = jnp.full((d,), 6.0, jnp.float32)
    elif link.compression == "pca":
        dim = link.pca_dim or d // 4
        w = dense_init(key, (dim, d), jnp.float32, scale=1.0)
        p["w"] = w
        p["b"] = jnp.zeros((d,), jnp.float32)
    return p


def init_lm(key, cfg: ModelConfig) -> Params:
    dtype = dtype_of(cfg.dtype)
    ks = split_keys(key, 6)
    p: Params = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "stack": transformer.init_stack(ks[1], cfg, dtype),
        "final_norm": init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "link": init_link_params(ks[3], cfg, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[4], (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.frontend:
        p["frontend"] = frontends.init_frontend_adapter(ks[5], cfg, dtype)
    return p


# ---------------------------------------------------------------------------
# Link layer constructors
# ---------------------------------------------------------------------------

def _compressor_from_params(cfg: ModelConfig, link_params: Params) -> Compressor:
    link = cfg.link
    if link.compression == "quant":
        return Compressor(
            kind="quant",
            quant=QuantSpec(
                bits=link.quant_bits,
                s_min=link_params["s_min"],
                s_max=link_params["s_max"],
            ),
        )
    if link.compression == "pca":
        return Compressor(
            kind="pca", pca=PCASpec(w=link_params["w"], b=link_params["b"])
        )
    return Compressor(kind="identity")


def link_spec_from_config(
    cfg: ModelConfig,
    loss_rate: Optional[float] = None,
    **overrides,
) -> comtune.LinkSpec:
    """The ``LinkSpec`` a model config implies (compressor left at its
    default — the calibrated one lives in the param pytree and is grafted
    on inside :func:`make_link_fn`)."""
    link = cfg.link
    spec_kwargs = dict(
        dropout_rate=link.dropout_rate,
        loss_rate=link.loss_rate if loss_rate is None else loss_rate,
        train_link=link.train_link,
        channel=link.channel,
        channel_params=tuple(link.channel_params),
        shuffle=link.shuffle,
        fec_k=link.fec_k,
        fec_m=link.fec_m,
        fec_kind=link.fec_kind,
    )
    spec_kwargs.update(overrides)
    return comtune.LinkSpec(**spec_kwargs)


def make_link_fn(
    cfg: ModelConfig,
    link_params: Params,
    key: Optional[jax.Array],
    mode: str,
    loss_rate: Optional[float] = None,
    link_spec: Optional[comtune.LinkSpec] = None,
    link_rate=None,
):
    """Build the function applied at the split point — a closure over
    ``comtune.emulate_link``, the one differentiable link path shared by
    training and serving.

    mode:
      "train"   -> Eq. 8:  STE-compressed roundtrip + the emulation picked
                   by ``spec.train_link`` (Eq. 7 dropout / full channel)
      "serve"   -> Eq. 12: compress -> channel(p) -> 1/(1-p) -> decompress
      "clean"   -> compression only, no loss (reliable-protocol reference)
      "off"     -> None (link disabled; plain model)

    ``link_spec`` (a full ``LinkSpec``, e.g. from the trainer's curriculum)
    takes precedence over the cfg-derived spec; its compressor field is
    replaced by the calibrated one carried in ``link_params`` either way.

    ``link_rate`` overrides the *emulation rate of the current mode* and
    may be a TRACED scalar — this is how the per-step curriculum feeds the
    ramped rate as scan data instead of a compile-time constant.  In train
    mode it sets whatever ``spec.train_link`` draws at (dropout rate or
    channel loss rate); in serve mode it sets the channel loss rate.
    Traced rates are only supported on the dropout / plain-iid paths (the
    stateful channels bake their rate into static transition tables).
    """
    if mode == "off":
        return None
    compressor = _compressor_from_params(cfg, link_params)
    if link_spec is None:
        link_spec = link_spec_from_config(cfg, loss_rate=loss_rate)
    elif loss_rate is not None:
        # Authoritative: also strips a channel_params ("loss_rate", x)
        # entry that would otherwise shadow the caller's rate.
        link_spec = link_spec.with_channel_loss_rate(loss_rate)
    if link_rate is not None:
        if mode == "train":
            link_spec = link_spec.with_train_rate(link_rate)
        else:
            link_spec = link_spec.with_channel_loss_rate(link_rate)
    spec = dataclasses.replace(link_spec, compressor=compressor)

    def fn(x):
        return comtune.emulate_link(key, x, spec, mode)

    return fn


def make_slotwise_link_fn(
    cfg: ModelConfig,
    link_params: Params,
    keys: jax.Array,                   # (B, 2) uint32 — one key per slot
    mode: str,
    loss_rate: Optional[float] = None,
    link_spec: Optional[comtune.LinkSpec] = None,
    live: Optional[jax.Array] = None,  # (B,) bool — weights for obs totals
):
    """Per-slot link for a *batched* decode step over shared state.

    The contiguous slot-pool engine vmaps the whole serve step, so each
    lane's :func:`make_link_fn` closure naturally draws from that lane's
    key.  The paged engine cannot vmap (the block pool is shared across
    slots), so this builds the equivalent batched link: the split-point
    activation ``(B, S, d)`` is vmapped row-by-row through
    ``comtune.emulate_link`` with per-slot keys — bitwise the same draws
    as the vmapped-engine form.  Each row's tap totals come out of the
    vmap as batched outputs and are re-published to the ambient collector
    weighted by ``live`` (matching the contiguous engine's live-masked
    counter accumulation; dead slots still compute, but never count).
    """
    if mode == "off":
        return None
    compressor = _compressor_from_params(cfg, link_params)
    if link_spec is None:
        link_spec = link_spec_from_config(cfg, loss_rate=loss_rate)
    elif loss_rate is not None:
        link_spec = link_spec.with_channel_loss_rate(loss_rate)
    spec = dataclasses.replace(link_spec, compressor=compressor)

    from repro.obs import device as obs_device

    def fn(x):                                       # (B, S, d)
        def one(k, xr):
            with obs_device.tap_link_stats() as tap:
                y = comtune.emulate_link(k, xr[None], spec, mode)
                totals = tap.totals()
            return y[0], totals

        y, totals = jax.vmap(one)(keys, x)
        w = (
            jnp.ones((x.shape[0],), jnp.float32)
            if live is None
            else live.astype(jnp.float32)
        )
        obs_device.emit(
            {name: jnp.sum(w * v) for name, v in totals.items()}
        )
        return y

    return fn


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(
    params: Params,
    tokens: jax.Array,                 # (B, S) int32
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    frontend_embed: Optional[jax.Array] = None,
    cache: Optional[Dict[str, Any]] = None,
    cache_index=None,
    link_key: Optional[jax.Array] = None,
    link_mode: str = "off",
    loss_rate: Optional[float] = None,
    link_spec: Optional[comtune.LinkSpec] = None,
    link_rate=None,
    link_fn=None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Returns (logits (B, S, V) float32, new_cache, moe_aux).

    ``link_spec`` carries the full emulated-link configuration (channel
    process, FEC, train-time emulation kind, curriculum rate); when omitted
    it is derived from ``cfg.link``.  ``link_rate`` (possibly traced)
    overrides the emulation rate — see :func:`make_link_fn`.  ``link_fn``
    replaces the link layer entirely with a caller-supplied callable
    (e.g. the eval hook forcing a *realized* delivery mask at the split)."""
    b, s = tokens.shape
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(jnp.float32(cfg.d_model)), x.dtype)
    if cfg.frontend and frontend_embed is not None:
        x = frontends.fuse_frontend(params["frontend"], x, frontend_embed)

    if positions is None:
        offset = cache_index if cache_index is not None else 0
        positions = rope_lib.default_positions(
            b, s, offset=offset, mrope=bool(cfg.mrope_sections)
        )

    if link_fn is None:
        link_fn = make_link_fn(
            cfg, params["link"], link_key, link_mode, loss_rate=loss_rate,
            link_spec=link_spec, link_rate=link_rate,
        )
    x, new_cache, aux = transformer.run_stack(
        params["stack"],
        x,
        cfg,
        positions,
        cache=cache,
        cache_index=cache_index,
        link_fn=link_fn,
        mode=mode,
    )
    with jax.named_scope("di_head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = x @ params["lm_head"]
        logits = logits.astype(jnp.float32)
    return logits, new_cache, aux


def lm_loss(
    logits: jax.Array, tokens: jax.Array, aux: jax.Array, aux_coef: float
) -> jax.Array:
    """Next-token cross entropy (shift-by-one) + MoE load-balance aux.

    Sharded-vocab-safe formulation: the target logit is extracted with a
    one-hot contraction over the (model-sharded) vocab dim and the logsumexp
    is a reduction — both lower to tiny (B, S) all-reduces.  The naive
    ``take_along_axis(log_softmax(...))`` gathers the full f32 logits across
    the mesh (measured: 2x40 GB/device/step on qwen1.5-0.5b x train_4k;
    see EXPERIMENTS.md §Perf iteration 1)."""
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(targets, lg.shape[-1], dtype=lg.dtype)
    target_logit = jnp.sum(lg * onehot, axis=-1)
    nll = lse - target_logit
    return nll.mean() + aux_coef * aux
