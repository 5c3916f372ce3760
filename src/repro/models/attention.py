"""Grouped-query attention with RoPE/M-RoPE, sliding windows and KV caches.

Softmax implementations, selected by ``cfg.attn_impl``:

* ``naive``        — materializes (Sq, Skv) scores; smoke tests.  At decode
                     (Sq == 1) it masks dead cache slots before softmax and,
                     when ``cache_index`` is a concrete int, slices the
                     valid prefix so only live positions are dequantized.
* ``blockwise``    — online-softmax over KV blocks inside a scan over Q
                     blocks (FlashAttention recurrence in pure jnp) for
                     train/prefill: activation memory is O(S · block)
                     instead of O(S²).  The Pallas kernel in
                     ``repro/kernels/flash_attention`` implements the same
                     recurrence with explicit VMEM tiling for TPU.
* ``flash_decode`` — train/prefill as ``blockwise``; the s == 1 decode step
                     runs ``repro/kernels/decode_attention`` — length-masked
                     online softmax that reads only ``ceil(valid/block)``
                     cache blocks and dequantizes int8 KV inline, making the
                     decode step O(valid tokens) instead of O(max_seq).
                     ``blockwise`` configs also take this decode path (it is
                     the production default the serve engines compile);
                     ``naive`` keeps the full-cache matvec as the oracle.

Sliding-window layers keep a **rotating KV cache** of ``window`` slots;
RoPE is applied at write time so cached keys need no absolute positions at
read time.  Rotating writes land at ``index % C``, so the live slots are
always the contiguous prefix ``[0, min(index + 1, C))`` — the one fact the
length-masked decode paths rely on.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import rope as rope_lib
from repro.models.common import Params, dense_init, split_keys, zeros_init

NEG_INF = -1.0e30


class PagedIndex(NamedTuple):
    """Paged-decode coordinates, passed as ``cache_index`` when the decode
    state is a block pool instead of a contiguous cache.

    The stack closes over ``cache_index`` (it is not a scan operand), so the
    static ``max_seq`` / ``block_size`` ints ride through ``run_stack``
    untouched and each layer derives its own rotating length from them.
    ``live`` routes dead slots' decode writes to the reserved trash block 0
    — with a shared pool, a retired slot's blocks may already belong to a
    new request, so dirty writes must land somewhere unowned."""

    lengths: jax.Array        # (B,) int32 — tokens already cached per slot
    block_table: jax.Array    # (B, J) int32 — physical block ids (0 = trash)
    live: jax.Array           # (B,) bool — slot currently owns its blocks
    max_seq: int
    block_size: int


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, h, kv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype),
        "w_out": dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _constrain_attention(qg, k, v, cfg: ModelConfig):
    """Pin q/k/v shardings for train/prefill attention when a production
    mesh context is active.  Preference order:
      1. shard KV heads over 'model' (contraction dims stay local);
      2. shard the batch over (data..., 'model') jointly — attention becomes
         fully per-example-local at the cost of one reshard per layer.
    Measured effect on arctic x train_4k: removes the 235 MB x 992 partial
    all-reduces inside the blockwise-attention loop."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import ctx as shard_ctx

    mesh = shard_ctx.shard_map_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return qg, k, v
    m = mesh.shape["model"]
    data_axes, _ = shard_ctx.mesh_axes(mesh)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    b, _, kvh, g, _ = qg.shape
    h = kvh * g
    bs = data_axes if (data_axes and b % n_data == 0) else None
    cons = lambda x, s: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, s)
    )

    if kvh % m == 0:
        q_spec = P(bs, None, "model", None, None)
        kv_spec = P(bs, None, "model", None)
        return cons(qg, q_spec), cons(k, kv_spec), cons(v, kv_spec)
    if h % m == 0 and g > 1:
        # Iteration 6: replicate KV heads up to H (2x KV memory for gemma3,
        # 8x for kimi) so the full query-head count shards over 'model'.
        bsz, s = qg.shape[0], qg.shape[1]
        hd = qg.shape[-1]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        qg = qg.reshape(bsz, s, h, 1, hd)
        q_spec = P(bs, None, "model", None, None)
        kv_spec = P(bs, None, "model", None)
        return cons(qg, q_spec), cons(k, kv_spec), cons(v, kv_spec)
    # Batch sharding over (data x model) was tried here and REFUTED:
    # the per-layer q/k/v+out reshard cost ~3x more than the partial
    # all-reduces it removed (arctic x train_4k: 39.3s -> 132s; see
    # EXPERIMENTS.md §Perf hillclimb 1 iteration 3).
    return qg, k, v


# ---------------------------------------------------------------------------
# Softmax attention cores
# ---------------------------------------------------------------------------

def _grouped(q: jax.Array, num_kv: int) -> jax.Array:
    """(B, S, H, hd) -> (B, S, KV, G, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, hd)


def _naive_attn(
    q: jax.Array,          # (B, Sq, KV, G, hd)
    k: jax.Array,          # (B, Skv, KV, hd)
    v: jax.Array,
    mask: jax.Array,       # broadcastable to (B, KV, G, Sq, Skv)
    softcap: float,
) -> jax.Array:
    hd = q.shape[-1]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out


def _blockwise_attn(
    q: jax.Array,          # (B, Sq, KV, G, hd)
    k: jax.Array,          # (B, Skv, KV, hd)
    v: jax.Array,
    *,
    causal: bool,
    window: int,
    q_offset,
    block_q: int,
    block_kv: int,
    softcap: float,
) -> jax.Array:
    """FlashAttention-style online softmax in pure jnp."""
    b, sq, kvh, g, hd = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    # Pad to block multiples.
    pad_q = (-sq) % bq
    pad_kv = (-skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    nq = q.shape[1] // bq
    nkv = k.shape[1] // bkv
    qb = q.reshape(b, nq, bq, kvh, g, hd)
    kb = k.reshape(b, nkv, bkv, kvh, hd)
    vb = v.reshape(b, nkv, bkv, kvh, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def q_block(qi, qblk):
        q_pos = q_offset + qi * bq + jnp.arange(bq)  # (bq,)

        def kv_step(carry, inputs):
            acc, m, l = carry
            kj, kblk, vblk = inputs
            k_pos = kj * bkv + jnp.arange(bkv)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qblk, kblk).astype(jnp.float32) * scale
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            msk = jnp.ones((bq, bkv), bool)
            if causal:
                msk &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                msk &= q_pos[:, None] - k_pos[None, :] < window
            msk &= (k_pos[None, :] < skv)  # kv padding
            s = jnp.where(msk[None, None, None, :, :], s, NEG_INF)
            s_max = jnp.max(s, axis=-1)                        # (b,kv,g,bq)
            m_new = jnp.maximum(m, s_max)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(msk[None, None, None, :, :], p, 0.0)
            correction = jnp.exp(m - m_new)
            l_new = l * correction + jnp.sum(p, axis=-1)
            acc_new = acc * correction[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(qblk.dtype), vblk
            ).astype(jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, kvh, g, bq, hd), jnp.float32)
        m0 = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        kjs = jnp.arange(nkv)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), (kjs, jnp.swapaxes(kb, 0, 1), jnp.swapaxes(vb, 0, 1))
        )
        out = acc / jnp.maximum(l[..., None], 1e-20)
        return jnp.einsum("bkgqh->bqkgh", out).astype(q.dtype)  # (b,bq,kv,g,hd)

    outs = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), jnp.swapaxes(qb, 0, 1)))
    out = jnp.swapaxes(outs, 0, 1).reshape(b, nq * bq, kvh, g, hd)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# Cache helpers (rotating buffer for windowed layers)
# ---------------------------------------------------------------------------

def cache_len(spec: LayerSpec, max_seq: int) -> int:
    return min(max_seq, spec.window) if spec.window > 0 else max_seq


def init_kv_cache(
    batch: int, length: int, num_kv: int, head_dim: int, dtype,
    kv_cache_dtype: str = "",
) -> Params:
    """bf16 cache, or int8 + per-(pos, head) bf16 scales (§Perf hillclimb 3:
    decode is HBM-bound on the cache read; int8 halves cache bytes).

    K and V hold one row of ``num_kv * head_dim`` lanes per position,
    ``(batch, length, num_kv * head_dim)``: the layout the decode kernel
    reads.  A TPU tiles the two minor dims of an array, so a
    ``(..., num_kv, head_dim)`` cache (16 x 64, or 4 x 128) would be padded
    in its tiles and relaid out for the kernel on every read."""
    rows = (batch, length, num_kv * head_dim)
    if kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros(rows, jnp.int8),
            "v": jnp.zeros(rows, jnp.int8),
            "k_scale": jnp.zeros((batch, length, num_kv), jnp.bfloat16),
            "v_scale": jnp.zeros((batch, length, num_kv), jnp.bfloat16),
        }
    return {"k": jnp.zeros(rows, dtype), "v": jnp.zeros(rows, dtype)}


def _quantize_kv(x: jax.Array):
    """(..., hd) -> int8 codes + per-(...,) bf16 scale (absmax)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    codes = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return codes.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _dequantize_kv(codes: jax.Array, scale: jax.Array, dtype):
    return (
        codes.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
    ).astype(dtype)


def _is_quantized(cache: Params) -> bool:
    return "k_scale" in cache


def _read_cache(cache: Params, dtype, num_kv: Optional[int] = None):
    """K and V as ``(B, C, KV, hd)``, dequantized.  ``num_kv`` splits the
    rows of a bf16 cache into heads (an int8 cache's scales give it)."""
    k, v = cache["k"], cache["v"]
    if k.ndim == 3:
        heads = cache["k_scale"].shape[-1] if _is_quantized(cache) else num_kv
        k = k.reshape(k.shape[:2] + (heads, -1))
        v = v.reshape(v.shape[:2] + (heads, -1))
    if _is_quantized(cache):
        return (
            _dequantize_kv(k, cache["k_scale"], dtype),
            _dequantize_kv(v, cache["v_scale"], dtype),
        )
    return k, v


def _cache_rows(cache: Params, k: jax.Array, v: jax.Array) -> Params:
    """New K/V ``(B, S, KV, hd)`` as the cache stores them: rows of
    ``KV * hd`` lanes, int8 codes plus per-head scales for an int8 cache."""
    rows = lambda a: a.reshape(a.shape[:2] + (-1,))
    if _is_quantized(cache):
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {"k": rows(kq), "v": rows(vq), "k_scale": ks, "v_scale": vs}
    return {"k": rows(k), "v": rows(v)}


def layer_of(cache: Params, unit) -> Params:
    """The layer's own cache: ``cache`` itself, or unit ``unit`` of a cache
    stacked over the stack's units (leading axis U) when ``unit`` is given."""
    if unit is None:
        return cache
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, unit, keepdims=False), cache
    )


def _write_rows(buf: jax.Array, val: jax.Array, row, unit) -> jax.Array:
    """Write ``val`` ``(B, S, ...)`` at positions ``row...`` of axis 1 of a
    layer's cache leaf ``buf``, or of unit ``unit`` of a stacked leaf: only
    the new rows move.  One row is a scatter: vmapped over the slot pool it
    stays one scatter, where a ``dynamic_update_slice`` becomes a loop
    over the slots."""
    if val.shape[1] == 1:
        at = (jnp.arange(val.shape[0]), row)
        return buf.at[at if unit is None else (unit,) + at].set(val[:, 0])
    if unit is None:
        return jax.lax.dynamic_update_slice_in_dim(buf, val, row, axis=1)
    start = (unit, 0, row) + (0,) * (val.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, val[None], start)


def _write_decode(cache: Params, k: jax.Array, v: jax.Array, index,
                  unit=None) -> Params:
    """Write one position (S==1) at rotating slot index % C (of unit
    ``unit`` when the cache is stacked over units)."""
    c = cache["k"].shape[1 if unit is None else 2]
    slot = index % c
    return {
        name: _write_rows(cache[name], val, slot, unit)
        for name, val in _cache_rows(cache, k, v).items()
    }


def _write_decode_paged(
    cache: Params, k: jax.Array, v: jax.Array, idx: PagedIndex, c_len: int,
    unit=None,
) -> Params:
    """Paged twin of :func:`_write_decode`: scatter each slot's one new
    position into its block-table row (of unit ``unit`` when the pool is
    stacked over units).  Logical row ``lengths % c_len`` (same rotation
    as contiguous) maps to block ``row // block_size``, offset
    ``row % block_size``; dead slots write trash block 0."""
    bs = idx.block_size
    row = idx.lengths % c_len                                    # (B,)
    ent = jnp.take_along_axis(
        idx.block_table, (row // bs)[:, None], axis=1
    )[:, 0]
    phys = jnp.where(idx.live, ent, 0)
    rin = row % bs

    def upd(buf, val):
        if unit is None:
            return buf.at[phys, rin].set(val[:, 0])
        return buf.at[unit, phys, rin].set(val[:, 0])

    return {
        name: upd(cache[name], val)
        for name, val in _cache_rows(cache, k, v).items()
    }


def _concrete_index(cache_index) -> Optional[int]:
    """``cache_index`` as a Python int when it is statically known (plain
    int or concrete jax scalar outside jit); None for tracers."""
    if isinstance(cache_index, (int, np.integer)):
        return int(cache_index)
    try:
        return int(cache_index)
    except (jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError, TypeError):
        return None


def _masked_decode_attn(
    qg: jax.Array, cache: Params, cache_index, softcap: float, dtype
) -> jax.Array:
    """Decode (s == 1) fallback: validity-masked ``_naive_attn`` over the
    rotating buffer.  When ``cache_index`` is concrete (e.g. the un-jitted
    reference loop) the valid prefix is sliced out FIRST, so only live
    positions are dequantized/read — the full-cache dequant the int8 cache
    otherwise pays every step.  Traced indices (every jitted engine) keep
    the fixed-shape masked form; they escape O(max_seq) via the
    ``flash_decode`` path instead."""
    c = cache["k"].shape[1]
    idx = _concrete_index(cache_index)
    if idx is not None:
        n_valid = min(idx + 1, c)
        cache = {name: buf[:, :n_valid] for name, buf in cache.items()}
        valid = jnp.ones((1, n_valid), bool)
    else:
        n_valid = jnp.minimum(cache_index + 1, c)  # scalar
        valid = jnp.arange(c)[None, :] < n_valid   # (1, C)
    mask = valid[:, None, None, None, :]           # (1,1,1,1,C) -> bcast
    k_read, v_read = _read_cache(cache, dtype, qg.shape[2])
    return _naive_attn(qg, k_read, v_read, mask, softcap)


def _write_prefill(cache: Params, k: jax.Array, v: jax.Array,
                   unit=None) -> Params:
    """Write a full prefill (positions 0..S-1) consistent with rotating
    decode writes: position p lands in slot p % C, keeping only the last C
    (into unit ``unit`` when the cache is stacked over units)."""
    c = cache["k"].shape[1 if unit is None else 2]
    s = k.shape[1]
    parts = _cache_rows(cache, k, v)
    if s <= c:
        return {
            name: _write_rows(cache[name], val, 0, unit)
            for name, val in parts.items()
        }
    slots = (jnp.arange(c) + (s - c)) % c
    out = {}
    for name, val in parts.items():
        layer = layer_of(cache[name], unit).at[:, slots].set(val[:, s - c :])
        out[name] = layer if unit is None else jax.lax.dynamic_update_index_in_dim(
            cache[name], layer, unit, 0
        )
    return out


# ---------------------------------------------------------------------------
# Layer forward
# ---------------------------------------------------------------------------

def attention_forward(
    p: Params,
    x: jax.Array,                      # (B, S, d)
    cfg: ModelConfig,
    spec: LayerSpec,
    positions: jax.Array,              # (B, S) or (B, 3, S)
    cache: Optional[Params] = None,
    cache_index=None,                  # scalar count of tokens already cached
    unit=None,                         # this layer's unit in a stacked cache
) -> Tuple[jax.Array, Optional[Params]]:
    """Returns (output, new cache).  With ``unit`` given, ``cache`` holds
    every unit's cache of this layer stacked on a leading axis: the layer
    writes its new K/V rows into unit ``unit`` of that stack, attends over
    that unit's panel, and returns the stack."""
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    q = rope_lib.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = rope_lib.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    qg = _grouped(q, kvh)
    if cache is None:
        # Production-mesh activation sharding for the blockwise loop
        # (EXPERIMENTS.md §Perf hillclimb 1, iterations 2+6): KV-head
        # sharding when divisible; else replicate KV heads up to the query
        # head count when THAT divides (gemma3/kimi/qwen2-vl GQA pattern) —
        # either way the online-softmax loop becomes communication-free.
        qg, k, v = _constrain_attention(qg, k, v, cfg)

    new_cache = None
    if cache is not None and s == 1 and isinstance(cache_index, PagedIndex):
        # ---- paged decode: scatter into the block pool, attend via the
        # block table.  Always the flash-decode kernel/ref — the block
        # pool has no contiguous layout for the naive oracle to read.
        idx = cache_index
        c = cache_len(spec, idx.max_seq)
        new_cache = _write_decode_paged(cache, k, v, idx, c, unit)
        from repro.kernels.decode_attention import paged_decode_attention

        n_valid = jnp.minimum(idx.lengths.astype(jnp.int32) + 1, c)
        out = paged_decode_attention(
            qg, layer_of(new_cache, unit), idx.block_table, n_valid,
            seq_len=c,
            block_size=idx.block_size,
            softcap=cfg.logit_softcap,
        )
    elif cache is not None and s == 1:
        # ---- decode: write one slot, attend over the rotating buffer ----
        new_cache = _write_decode(cache, k, v, cache_index, unit)
        layer = layer_of(new_cache, unit)
        if cfg.attn_impl in ("flash_decode", "blockwise"):
            # Length-masked flash decode: O(valid) cache blocks read,
            # int8 KV dequantized inline — the serve engines' default.
            from repro.kernels.decode_attention import decode_attention

            c = layer["k"].shape[1]
            n_valid = jnp.minimum(
                jnp.asarray(cache_index, jnp.int32) + 1, c
            )
            out = decode_attention(
                qg, layer, n_valid,
                softcap=cfg.logit_softcap,
                block_kv=cfg.attn_decode_block_kv,
            )
        else:
            out = _masked_decode_attn(
                qg, layer, cache_index, cfg.logit_softcap, k.dtype
            )
    else:
        # ---- train / prefill: self-attention over the fresh sequence ----
        if cfg.attn_impl in ("blockwise", "flash_decode") and s > cfg.attn_block_q:
            out = _blockwise_attn(
                qg,
                k,
                v,
                causal=True,
                window=spec.window,
                q_offset=0,
                block_q=cfg.attn_block_q,
                block_kv=cfg.attn_block_kv,
                softcap=cfg.logit_softcap,
            )
        else:
            q_pos = jnp.arange(s)
            msk = q_pos[:, None] >= q_pos[None, :]
            if spec.window > 0:
                msk &= q_pos[:, None] - q_pos[None, :] < spec.window
            out = _naive_attn(
                qg, k, v, msk[None, None, None, :, :], cfg.logit_softcap
            )
        if cache is not None:
            new_cache = _write_prefill(cache, k, v, unit)

    out = out.reshape(b, s, h * hd)
    return out @ p["w_out"], new_cache
