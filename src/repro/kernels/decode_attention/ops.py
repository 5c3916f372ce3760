"""Public decode-attention entry point: cache-layout front-end + backend
dispatch (Pallas kernel on accelerators, bit-identical jnp fallback on CPU).

``decode_attention`` consumes the model's decode state directly — the
grouped query ``(B, 1, KV, G, hd)`` and the rotating cache dict in its
native layout, rows of ``KV * hd`` lanes ``(B, C, KV * hd)`` (a
``(B, C, KV, hd)`` cache is taken too), or the layer's cache stacked over
the stack's units ``(U, B, C, KV * hd)`` with the unit to read, int8
codes + scales or bf16 — so no transposed, dequantized or per-layer copy
of the cache is ever materialized.  Dispatch:

* ``REPRO_FLASH_DECODE_IMPL=kernel|ref`` forces a path (tests/benchmarks);
* otherwise the jnp fallback on CPU (a compiled interpret-mode Pallas call
  would be orders of magnitude slower than the identical-math jnp program)
  and the real kernel elsewhere (interpret resolution per
  ``kernels.runtime.pallas_interpret``).

Both paths are vmap-able over a leading slot axis with per-slot
``n_valid`` — this is how the continuous-batching engine's fused decode
step runs one length-masked attention per in-flight request; the kernel
path stays one ``pallas_call`` over every slot under that vmap.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_block_kv,
    flash_decode_kernel,
    paged_flash_decode_kernel,
)
from repro.kernels.decode_attention.ref import (
    flash_decode_ref,
    paged_flash_decode_ref,
)

IMPL_ENV_VAR = "REPRO_FLASH_DECODE_IMPL"


def _impl(override: Optional[str] = None) -> str:
    choice = (override or os.environ.get(IMPL_ENV_VAR, "") or "").strip().lower()
    if choice in ("kernel", "ref"):
        return choice
    if choice:
        raise ValueError(
            f"unknown decode-attention impl {choice!r} (from "
            f"{'impl=' if override else IMPL_ENV_VAR}) — want 'kernel' or "
            "'ref'; unset for backend auto-detection"
        )
    return "ref" if jax.default_backend() == "cpu" else "kernel"


def _heads(x: jax.Array, kvh: int) -> jax.Array:
    """Cache rows ``(B, C, KV * hd)`` as ``(B, C, KV, hd)``: the jnp
    fallback walks one head at a time."""
    return x.reshape(x.shape[:2] + (kvh, -1))


def decode_attention(
    q: jax.Array,                        # (B, 1, KV, G, hd) grouped query
    cache: Dict[str, Any],               # k/v (B, C, KV*hd) [+ k/v_scale]
    n_valid: jax.Array,                  # scalar or (B,) live-slot count
    *,
    unit=None,
    softcap: float = 0.0,
    block_kv: Optional[int] = None,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Length-masked decode attention over the rotating cache.

    Returns ``(B, 1, KV, G, hd)`` in ``q.dtype`` — a drop-in for the
    decode branch of ``models.attention.attention_forward``.  With
    ``unit`` given, ``cache`` is the layer's cache stacked over the stack's
    units, ``(U, B, C, KV*hd)``, and unit ``unit`` of it is attended where
    it lies; a cache without ``unit`` is a stack of one.  Only cache blocks
    below ``ceil(n_valid / block)`` are read (and, for int8 caches,
    dequantized — inline, per block, in f32).  The block is
    :func:`decode_block_kv` of the cache's shape; ``block_kv`` overrides it
    (tests walk tiny caches in several blocks so).
    """
    b, s, kvh, g, hd = q.shape
    assert s == 1, f"decode attention is the s == 1 path, got S={s}"
    k, v = cache["k"], cache["v"]
    k_scale = cache.get("k_scale")
    v_scale = cache.get("v_scale")
    if unit is None:
        unit = 0
        k, v = k.reshape(1, b, -1, kvh * hd), v.reshape(1, b, -1, kvh * hd)
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    c = k.shape[2]
    bkv = min(block_kv or decode_block_kv(c, kvh * hd, k.dtype.itemsize), c)
    pad = (-c) % bkv
    if pad:
        # No divisor of the cache length is a whole number of tiles: pad
        # the position axis to a block multiple.  Padded rows sit at
        # k_pos >= C >= n_valid, so the validity mask never reads them;
        # the copy only happens for lengths the engines never produce.
        grow = lambda a: jnp.pad(
            a, ((0, 0), (0, 0), (0, pad), (0, 0))
        )
        k, v = grow(k), grow(v)
        if k_scale is not None:
            k_scale, v_scale = grow(k_scale), grow(v_scale)
    n = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1), (b,))
    qh = q[:, 0]                                             # (B, KV, G, hd)
    if _impl(impl) == "kernel":
        out = flash_decode_kernel(
            qh, k, v, k_scale, v_scale, n, unit,
            block_kv=bkv, softcap=softcap, interpret=interpret,
        )
    else:
        at = lambda a: None if a is None else jax.lax.dynamic_index_in_dim(
            a, unit, keepdims=False
        )
        out = flash_decode_ref(
            qh, _heads(at(k), kvh), _heads(at(v), kvh), at(k_scale),
            at(v_scale), n[:, None], block_kv=bkv, softcap=softcap,
        )
    return out[:, None]


def paged_decode_attention(
    q: jax.Array,                        # (B, 1, KV, G, hd) grouped query
    pool: Dict[str, Any],                # k/v (N, bs, KV*hd) [+ k/v_scale]
    block_table: jax.Array,              # (B, J_max) int32 physical blocks
    n_valid: jax.Array,                  # (B,) live-row count per request
    *,
    seq_len: int,                        # this layer's rotating cache length
    block_size: int,
    softcap: float = 0.0,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Length-masked decode attention over a shared block pool.

    The paged twin of :func:`decode_attention`: same return contract
    ``(B, 1, KV, G, hd)`` in ``q.dtype``, but K/V rows live in
    ``(num_blocks, block_size, KV * hd)`` pool buffers addressed through
    each request's block-table row.  ``seq_len`` is static (the layer's
    ``cache_len``), so the table is sliced to this layer's
    ``ceil(seq_len / block_size)`` walkable blocks at trace time —
    windowed layers never index past their own rotation, and the padded
    tail rows of a short last block stay behind the ``k_pos < n_valid``
    mask (``n_valid <= seq_len``).  No pad/copy path is needed here: pool
    blocks are whole by construction.
    """
    b, s, kvh, g, hd = q.shape
    assert s == 1, f"decode attention is the s == 1 path, got S={s}"
    k, v = pool["k"], pool["v"]
    k_scale = pool.get("k_scale")
    v_scale = pool.get("v_scale")
    assert k.shape[1] == block_size, (k.shape, block_size)
    j_l = -(-seq_len // block_size)
    assert block_table.shape[1] >= j_l, (block_table.shape, j_l)
    bt = jnp.asarray(block_table, jnp.int32)[:, :j_l]
    n = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1), (b,))
    qh = q[:, 0]                                             # (B, KV, G, hd)
    if _impl(impl) == "kernel":
        out = paged_flash_decode_kernel(
            qh, k, v, k_scale, v_scale, bt, n,
            block_size=block_size, softcap=softcap, interpret=interpret,
        )
    else:
        out = paged_flash_decode_ref(
            qh, _heads(k, kvh), _heads(v, kvh), k_scale, v_scale, bt, n,
            block_size=block_size, softcap=softcap,
        )
    return out[:, None]
