"""Unified decoder stack over heterogeneous layer kinds.

The stack is a repeating ``unit_pattern`` of layers scanned with ``lax.scan``
across ``U`` units (stacked params, leading axis U) plus an unrolled
``prologue``.  The COMtune link layer splits the unit scan in two — the
device-side scan and the server-side scan — so the split point is a
first-class part of the lowered program.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention, mamba, mlp, moe, xlstm
from repro.models.common import Params, apply_norm, init_norm, split_keys


# ---------------------------------------------------------------------------
# Per-layer init / forward
# ---------------------------------------------------------------------------

def _has_ffn(cfg: ModelConfig, spec: LayerSpec) -> bool:
    return spec.moe or cfg.d_ff > 0


def init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> Params:
    ks = split_keys(key, 4)
    p: Params = {"norm1": init_norm(ks[0], cfg.d_model, cfg.norm, dtype)}
    if spec.kind == "attn":
        p["mix"] = attention.init_attention(ks[1], cfg, dtype)
    elif spec.kind == "mamba":
        p["mix"] = mamba.init_mamba(ks[1], cfg, dtype)
    elif spec.kind == "mlstm":
        p["mix"] = xlstm.init_mlstm(ks[1], cfg, dtype)
    elif spec.kind == "slstm":
        p["mix"] = xlstm.init_slstm(ks[1], cfg, dtype)
    else:
        raise ValueError(spec.kind)
    if _has_ffn(cfg, spec):
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm, dtype)
        if spec.moe:
            p["ffn"] = moe.init_moe(ks[3], cfg, dtype)
        else:
            p["ffn"] = mlp.init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def layer_forward(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    spec: LayerSpec,
    positions: jax.Array,
    cache: Optional[Params],
    cache_index,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Pre-norm residual layer. Returns (x, new_cache, aux_loss)."""
    h_in = apply_norm(p["norm1"], x, cfg.norm)
    if spec.kind == "attn":
        h, new_cache = attention.attention_forward(
            p["mix"], h_in, cfg, spec, positions, cache, cache_index
        )
    elif spec.kind == "mamba":
        h, new_cache = mamba.mamba_forward(p["mix"], h_in, cfg, cache)
    elif spec.kind == "mlstm":
        if cache is not None and x.shape[1] == 1:
            h, new_cache = xlstm.mlstm_step(p["mix"], h_in, cfg, cache)
        else:
            # chunkwise-parallel form: O(S*chunk) memory instead of O(S^2)
            # (§Perf hillclimb 2); returns the exact recurrent state.
            h, st = xlstm.mlstm_chunked(p["mix"], h_in, cfg, cache)
            new_cache = st if cache is not None else None
    elif spec.kind == "slstm":
        h, new_cache = xlstm.slstm_forward(p["mix"], h_in, cfg, cache)
    else:
        raise ValueError(spec.kind)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if _has_ffn(cfg, spec):
        y_in = apply_norm(p["norm2"], x, cfg.norm)
        if spec.moe:
            y, aux = moe.moe_forward(p["ffn"], y_in, cfg)
        else:
            y = mlp.mlp_forward(p["ffn"], y_in, cfg.act, cfg.gated_mlp)
        x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack init
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, dtype) -> Params:
    u = cfg.resolved_num_units
    k_pro, k_units = jax.random.split(key)
    prologue = [
        init_layer(k, cfg, spec, dtype)
        for k, spec in zip(split_keys(k_pro, max(1, len(cfg.prologue))), cfg.prologue)
    ]
    unit_keys = jax.random.split(k_units, u)

    def init_unit(k):
        ks = split_keys(k, len(cfg.unit_pattern))
        return [init_layer(kk, cfg, spec, dtype) for kk, spec in zip(ks, cfg.unit_pattern)]

    units = jax.vmap(init_unit)(unit_keys)  # leaves: (U, ...)
    return {"prologue": prologue, "units": units}


# ---------------------------------------------------------------------------
# Stack forward (two scan segments around the link split)
# ---------------------------------------------------------------------------

def _unit_body(cfg: ModelConfig, positions, cache_index, with_cache: bool):
    """Returns a scan body over one unit of layers."""

    def body_fixed(carry, xs):
        x, aux = carry
        if with_cache:
            unit_params, unit_cache = xs
        else:
            unit_params, unit_cache = xs, [None] * len(cfg.unit_pattern)
        new_caches = []
        for j, spec in enumerate(cfg.unit_pattern):
            x, nc, a = layer_forward(
                unit_params[j], x, cfg, spec, positions, unit_cache[j], cache_index
            )
            aux = aux + a
            new_caches.append(nc)
        return (x, aux), (new_caches if with_cache else None)

    return body_fixed


def _slice_units(tree, lo: int, hi: int):
    return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)


def run_stack(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    cache: Optional[Dict[str, Any]] = None,
    cache_index=None,
    link_fn=None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Run prologue + unit scans, applying ``link_fn`` (the COMtune link
    layer) at the configured split point.  Returns (x, new_cache, aux)."""
    u = cfg.resolved_num_units
    split = min(max(cfg.link.split_after_units, 0), u) if link_fn is not None else 0
    aux = jnp.zeros((), jnp.float32)
    with_cache = cache is not None

    # Device scopes (``jax.named_scope``) name the split's parts in the
    # compiled program's op metadata, so a profile attributes device time to
    # them: ``di_device_half``, ``di_link``, ``di_server_half``, and the
    # per-segment slicing of weights and caches (``stack_split``) and the
    # caches' concatenation (``stack_merge``).
    # --- prologue (unrolled) ---
    new_pro = []
    with jax.named_scope("di_device_half"):
        for i, spec in enumerate(cfg.prologue):
            c_i = cache["prologue"][i] if with_cache else None
            x, nc, a = layer_forward(
                params["prologue"][i], x, cfg, spec, positions, c_i, cache_index
            )
            aux = aux + a
            new_pro.append(nc)

    body = _unit_body(cfg, positions, cache_index, with_cache)
    if mode == "train" and cfg.remat:
        body = jax.checkpoint(body)

    def scan_segment(x, aux, lo, hi, scope):
        if hi <= lo:
            return x, aux, None
        with jax.named_scope("stack_split"):
            xs = _slice_units(params["units"], lo, hi)
            if with_cache:
                xs = (xs, [_slice_units(c, lo, hi) for c in cache["units"]])
        with jax.named_scope(scope):
            (x, aux), ys = jax.lax.scan(body, (x, aux), xs)
        return x, aux, ys

    x, aux, ys1 = scan_segment(
        x, aux, 0, split if link_fn is not None else 0, "di_device_half"
    )
    if link_fn is not None:
        with jax.named_scope("di_link"):
            x = link_fn(x)
    x, aux, ys2 = scan_segment(x, aux, split, u, "di_server_half")

    new_cache = None
    if with_cache:
        segs = [s for s in (ys1, ys2) if s is not None]
        if len(segs) == 2:
            with jax.named_scope("stack_merge"):
                new_units = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b], axis=0), segs[0], segs[1]
                )
        else:
            new_units = segs[0]
        new_cache = {"prologue": new_pro, "units": new_units}
    return x, new_cache, aux
