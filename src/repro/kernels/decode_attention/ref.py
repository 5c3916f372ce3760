"""Pure-jnp length-masked flash-decode fallback — the CPU production path.

This is NOT a naive oracle: it runs ``kernel.py``'s arithmetic per head
(same f32 dequant, same ``preferred_element_type``, same mask/where
order, same online-softmax update expressions, same ``fori_loop`` bound
``ceil(n_valid / block_kv)``) — the kernel batches a head block into one
block-diagonal matmul whose extra terms are exact zeros — so CPU CI
exercises the same arithmetic recipe the accelerator kernel runs, at the
kernel's O(valid) cost: the traced loop bound lowers to a
``while_loop``, so blocks past the valid prefix are never read or
dequantized.  Against the interpret-mode kernel the outputs agree to
float-ulp level (~2e-6 in f32, pinned by tests) — the only residual
difference is XLA CPU fusion/FMA reassociation, which varies between any
two lowered programs and is not controllable from jnp.  The naive
full-cache oracle lives in ``models.attention._naive_attn``; tests
triangulate kernel ~= ref ~= naive.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1.0e30


def _decode_one(q, k, v, k_scale, v_scale, n_valid, *, block_kv, softcap):
    """One (request, kv-head): q (G, hd) vs k/v (C, hd) [+ scales (C,)]."""
    g, hd = q.shape
    q = q.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    quantized = k_scale is not None
    n_blocks = (n_valid + block_kv - 1) // block_kv

    def body(kj, carry):
        acc, m, l = carry
        start = kj * block_kv
        kb = jax.lax.dynamic_slice_in_dim(k, start, block_kv).astype(jnp.float32)
        vb = jax.lax.dynamic_slice_in_dim(v, start, block_kv).astype(jnp.float32)
        if quantized:
            kb = kb * jax.lax.dynamic_slice_in_dim(
                k_scale, start, block_kv
            ).astype(jnp.float32)[:, None]
            vb = vb * jax.lax.dynamic_slice_in_dim(
                v_scale, start, block_kv
            ).astype(jnp.float32)[:, None]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                            # (G, bkv)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = start + jax.lax.iota(jnp.int32, block_kv)
        msk = (k_pos < n_valid)[None, :]
        s = jnp.where(msk, s, NEG_INF)
        s_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(msk, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc * corr[:, None] + pv, m_new, l_new

    acc0 = jnp.zeros((g, hd), jnp.float32)
    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    return acc / jnp.maximum(l, 1e-20)[:, None]


def flash_decode_ref(
    q: jax.Array,                        # (B, KV, G, hd)
    k: jax.Array,                        # (B, C, KV, hd)
    v: jax.Array,
    k_scale: Optional[jax.Array],        # (B, C, KV) or None
    v_scale: Optional[jax.Array],
    n_valid: jax.Array,                  # (B, 1) int32
    *,
    block_kv: int = 64,
    softcap: float = 0.0,
) -> jax.Array:
    c = k.shape[1]
    assert c % block_kv == 0, (c, block_kv)
    one = functools.partial(_decode_one, block_kv=block_kv, softcap=softcap)
    # inner: map the kv-head axis (q axis 0; cache axis 1; scale axis 1)
    per_head = jax.vmap(one, in_axes=(0, 1, 1, 1 if k_scale is not None else None,
                                      1 if v_scale is not None else None, None))
    # outer: map the request/batch axis (n_valid (1,) -> scalar)
    out = jax.vmap(
        lambda qq, kk, vv, ks, vs, nn: per_head(qq, kk, vv, ks, vs, nn[0])
    )(q, k, v, k_scale, v_scale, n_valid)
    return out.astype(q.dtype)                               # (B, KV, G, hd)


def _paged_one(q, k_pool, v_pool, k_scale, v_scale, bt, n_valid, *,
               block_size, softcap):
    """One (request, kv-head): q (G, hd) vs pools (N, bs, hd) [+ scales
    (N, bs)] through the block-table row ``bt`` (J,) int32.  Identical
    arithmetic to :func:`_decode_one` — only the block fetch changes from
    a contiguous ``dynamic_slice`` to a table-indexed ``dynamic_index``,
    mirroring the paged kernel's SMEM-resolved index map."""
    g, hd = q.shape
    q = q.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    quantized = k_scale is not None
    n_blocks = (n_valid + block_size - 1) // block_size

    def body(kj, carry):
        acc, m, l = carry
        pid = jax.lax.dynamic_index_in_dim(bt, kj, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(
            k_pool, pid, keepdims=False
        ).astype(jnp.float32)                                # (bs, hd)
        vb = jax.lax.dynamic_index_in_dim(
            v_pool, pid, keepdims=False
        ).astype(jnp.float32)
        if quantized:
            kb = kb * jax.lax.dynamic_index_in_dim(
                k_scale, pid, keepdims=False
            ).astype(jnp.float32)[:, None]
            vb = vb * jax.lax.dynamic_index_in_dim(
                v_scale, pid, keepdims=False
            ).astype(jnp.float32)[:, None]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                            # (G, bs)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = kj * block_size + jax.lax.iota(jnp.int32, block_size)
        msk = (k_pos < n_valid)[None, :]
        s = jnp.where(msk, s, NEG_INF)
        s_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(msk, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc * corr[:, None] + pv, m_new, l_new

    acc0 = jnp.zeros((g, hd), jnp.float32)
    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    return acc / jnp.maximum(l, 1e-20)[:, None]


def paged_flash_decode_ref(
    q: jax.Array,                        # (B, KV, G, hd)
    k: jax.Array,                        # (N, bs, KV, hd) block pool
    v: jax.Array,
    k_scale: Optional[jax.Array],        # (N, bs, KV) or None
    v_scale: Optional[jax.Array],
    block_table: jax.Array,              # (B, J) int32
    n_valid: jax.Array,                  # (B,) int32
    *,
    block_size: int,
    softcap: float = 0.0,
) -> jax.Array:
    assert k.shape[1] == block_size, (k.shape, block_size)
    one = functools.partial(_paged_one, block_size=block_size, softcap=softcap)
    # inner: map the kv-head axis (q axis 0; pool axis 2; scale axis 2);
    # the block table and n_valid are shared across heads
    per_head = jax.vmap(one, in_axes=(0, 2, 2, 2 if k_scale is not None else None,
                                      2 if v_scale is not None else None,
                                      None, None))
    # outer: map the request axis; the pool itself is shared (closed over)
    out = jax.vmap(
        lambda qq, bt, nn: per_head(qq, k, v, k_scale, v_scale, bt, nn),
        in_axes=(0, 0, 0),
    )(q, block_table, n_valid)
    return out.astype(q.dtype)                               # (B, KV, G, hd)
