"""Unified decoder stack over heterogeneous layer kinds.

The stack is a repeating ``unit_pattern`` of layers looped over ``U`` units
(stacked params, leading axis U) plus an unrolled ``prologue``.  The
COMtune link layer splits the unit loop in two — the device-side loop and
the server-side loop — so the split point is a first-class part of the
lowered program.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

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


def _recurrent_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                       spec: LayerSpec, state: Optional[Params]):
    """The mixer of a recurrent layer.  Returns (h, new_state)."""
    if spec.kind == "mamba":
        return mamba.mamba_forward(p, x, cfg, state)
    if spec.kind == "mlstm":
        if state is not None and x.shape[1] == 1:
            return xlstm.mlstm_step(p, x, cfg, state)
        # chunkwise-parallel form: O(S*chunk) memory instead of O(S^2)
        # (§Perf hillclimb 2); returns the exact recurrent state.
        h, st = xlstm.mlstm_chunked(p, x, cfg, state)
        return h, (st if state is not None else None)
    if spec.kind == "slstm":
        return xlstm.slstm_forward(p, x, cfg, state)
    raise ValueError(spec.kind)


def layer_forward(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    spec: LayerSpec,
    positions: jax.Array,
    cache: Optional[Params],
    cache_index,
    unit=None,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Pre-norm residual layer. Returns (x, new_cache, aux_loss).

    With ``unit`` given, ``cache`` is this layer's cache stacked over the
    stack's units and only unit ``unit`` of it is read and written: new
    K/V rows for attention, the whole (small) state for recurrent layers.
    The returned cache is then the stack."""
    h_in = apply_norm(p["norm1"], x, cfg.norm)
    if spec.kind == "attn":
        h, new_cache = attention.attention_forward(
            p["mix"], h_in, cfg, spec, positions, cache, cache_index, unit
        )
    elif unit is None:
        h, new_cache = _recurrent_forward(p["mix"], h_in, cfg, spec, cache)
    else:
        h, state = _recurrent_forward(
            p["mix"], h_in, cfg, spec, attention.layer_of(cache, unit)
        )
        new_cache = jax.tree_util.tree_map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(a, b, unit, 0),
            cache, state,
        )
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
# Stack forward (two loops around the link split)
# ---------------------------------------------------------------------------

def _unit_body(cfg: ModelConfig, positions):
    """Scan body over one unit of layers, its weights as ``xs`` (no cache)."""

    def body(carry, unit_params):
        x, aux = carry
        for j, spec in enumerate(cfg.unit_pattern):
            x, _, a = layer_forward(
                unit_params[j], x, cfg, spec, positions, None, None
            )
            aux = aux + a
        return (x, aux), None

    return body


def _slice_units(tree, lo: int, hi: int):
    return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)


def _carried_units(params, cfg: ModelConfig, positions, cache_index, lo, hi,
                   x, aux, caches):
    """Units ``[lo, hi)`` over the whole stacked weights and the carried
    stacked caches: unit ``l``'s weights are read at ``l`` and only what
    unit ``l`` changes is written back into ``caches``."""

    def body(l, carry):
        x, aux, caches = carry
        unit_params = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False),
            params,
        )
        caches = list(caches)
        for j, spec in enumerate(cfg.unit_pattern):
            x, caches[j], a = layer_forward(
                unit_params[j], x, cfg, spec, positions, caches[j],
                cache_index, unit=l,
            )
            aux = aux + a
        return x, aux, caches

    return jax.lax.fori_loop(lo, hi, body, (x, aux, list(caches)))


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
    """Run prologue + unit loops, applying ``link_fn`` (the COMtune link
    layer) at the configured split point.  Returns (x, new_cache, aux).

    With a cache, both loops run over absolute unit indices of the whole
    stacked weights and carry the whole stacked caches, so nothing is
    copied at the split.  Without one (training, plain forwards), each
    side scans its slice of the weights, whose gradients the scan stacks
    per unit."""
    u = cfg.resolved_num_units
    split = min(max(cfg.link.split_after_units, 0), u) if link_fn is not None else 0
    aux = jnp.zeros((), jnp.float32)
    with_cache = cache is not None

    # Device scopes (``jax.named_scope``) name the split's parts in the
    # compiled program's op metadata, so a profile attributes device time to
    # them: ``di_device_half``, ``di_link``, ``di_server_half``, and, in
    # programs without a cache, the per-segment slicing of the weights
    # (``stack_split``).
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

    caches = cache["units"] if with_cache else None
    body = _unit_body(cfg, positions)
    if mode == "train" and cfg.remat:
        body = jax.checkpoint(body)

    def segment(x, aux, caches, lo, hi, scope):
        if hi <= lo:
            return x, aux, caches
        if with_cache:
            with jax.named_scope(scope):
                return _carried_units(
                    params["units"], cfg, positions, cache_index, lo, hi,
                    x, aux, caches,
                )
        with jax.named_scope("stack_split"):
            xs = _slice_units(params["units"], lo, hi)
        with jax.named_scope(scope):
            (x, aux), _ = jax.lax.scan(body, (x, aux), xs)
        return x, aux, caches

    x, aux, caches = segment(x, aux, caches, 0, split, "di_device_half")
    if link_fn is not None:
        with jax.named_scope("di_link"):
            x = link_fn(x)
    x, aux, caches = segment(x, aux, caches, split, u, "di_server_half")

    new_cache = {"prologue": new_pro, "units": caches} if with_cache else None
    return x, new_cache, aux
