"""Flash-decode forward kernels (Pallas): length-masked online-softmax
attention for the s == 1 decode step, with inline int8 dequantization.

Each wrapper reads the model's cache layout as it is, lane-dense:

* q        — (B, KV, G, hd)   one query token, GQA-grouped
* k / v    — (B, C, KV*hd)    rotating cache buffer (int8 codes or bf16),
                              one row of KV*hd lanes per position (a
                              (B, C, KV, hd) buffer is reshaped to it)
* k/v scale— (B, C, KV)       per-(pos, head) bf16 absmax scales (int8 only)
* n_valid  — (B, 1) int32     count of live cache slots for this request

TPU tiles the last two dims of every VMEM block by (8, 128), so a block
that picks one KV head out of ``(C, KV, hd)`` is refused.  Here the
minor dim is the flattened ``KV*hd`` lane axis, cut into *head blocks*
of ``hpb`` whole heads whose lanes fill a multiple of 128 (hd 64 -> two
heads per 128 lanes; hd 128 -> one), or all heads when no such cut
exists (then the block spans the whole array dim, which is always legal).
One head block is attended with plain 2-D matmuls: the wrapper expands
the query into a block-diagonal ``(hpb*G, hpb*hd)`` tile whose row of
head j is zero outside head j's lanes, so ``q @ k.T`` sums each row over
its own head only, and ``p @ v`` leaves head j's output in head j's lanes
(the other lanes of that row are discarded by the wrapper).  int8 scales
are spread onto the lanes by a ``(bkv, KV) @ (KV, lanes)`` 0/1 selector
matmul at full precision, which copies each scale exactly.

Contiguous kernel: grid (B, head blocks); the body walks KV blocks with a
``fori_loop`` whose upper bound is ``ceil(n_valid / block_kv)`` (``n_valid``
in SMEM) — blocks past the valid prefix are never computed on or
dequantized.  HBM traffic: each grid step's BlockSpec DMAs the whole
``(C, hpb*hd)`` K and V panels of its head block, so the contiguous kernel
reads the full cache (O(max_seq) bytes); the scale block ``(C, KV)`` keeps
its index across a request's head blocks and is fetched once per request.
Paged kernel: reads only the walked blocks (see below).

Rotating sliding-window caches need no extra handling: writes land at
``index % C`` (``models.attention._write_decode``), so the live slots are
always the contiguous prefix ``[0, min(index + 1, C))``.  Cached keys carry
RoPE from write time and softmax is permutation-invariant over slots.

The contiguous kernel is vmap-able (the slot-pool engine vmaps it over the
slot axis with a per-slot ``n_valid``); ``ref.py`` runs the same f32
arithmetic per head, so the jnp fallback agrees with the interpret-mode
kernel to float-ulp level (XLA summation order is the only difference).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import pallas_interpret

NEG_INF = -1.0e30
LANES = 128


def _heads_per_block(kvh: int, hd: int) -> int:
    """Fewest whole heads whose lanes fill a multiple of 128; all heads
    when no divisor of ``kvh`` does."""
    for hpb in range(1, kvh + 1):
        if kvh % hpb == 0 and (hpb * hd) % LANES == 0:
            return hpb
    return kvh


def _expand_q(q: jax.Array, hpb: int) -> jax.Array:
    """(B, KV, G, hd) -> block-diagonal (B, KV/hpb, hpb*G, hpb*hd)."""
    b, kvh, g, hd = q.shape
    qr = q.reshape(b, kvh // hpb, hpb, g, 1, hd)
    diag = jnp.eye(hpb, dtype=bool).reshape(1, 1, hpb, 1, hpb, 1)
    qx = jnp.where(diag, qr, jnp.zeros((), q.dtype))
    return qx.reshape(b, kvh // hpb, hpb * g, hpb * hd)


def _collapse_out(o: jax.Array, hpb: int, g: int, hd: int) -> jax.Array:
    """Inverse of :func:`_expand_q`: keep head j's lanes of head j's rows."""
    b, nhb = o.shape[:2]
    o6 = o.reshape(b, nhb, hpb, g, hpb, hd)
    diag = jnp.diagonal(o6, axis1=2, axis2=4)                # (b, nhb, g, hd, hpb)
    return jnp.moveaxis(diag, -1, 2).reshape(b, nhb * hpb, g, hd)


def _lane_selector(kvh: int, hpb: int, hd: int, hb) -> jax.Array:
    """(KV, hpb*hd) f32: 1 where lane belongs to head h of head block hb."""
    shape = (kvh, hpb * hd)
    local = jax.lax.broadcasted_iota(jnp.int32, shape, 0) - hb * hpb
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return ((lane >= local * hd) & (lane < local * hd + hd)).astype(jnp.float32)


def _dequant(codes, scales, sel):
    """int8 codes (bkv, lanes) * per-(pos, head) scales (bkv, KV) spread
    onto the lanes by the 0/1 selector — exact at HIGHEST precision."""
    lanes = jax.lax.dot_general(
        scales.astype(jnp.float32), sel, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return codes.astype(jnp.float32) * lanes


def _attend(q, k, v, pos0, n_valid, carry, *, softcap: float, scale):
    """One online-softmax update of rows q (R, L) over k/v (bkv, L)."""
    acc, m, l = carry                                        # (R,L) (R,1) (R,1)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                                # (R, bkv)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    k_pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    msk = k_pos < n_valid
    s = jnp.where(msk, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(msk, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return acc * corr + pv, m_new, l_new


def _make_kernel(*, block_kv, softcap, quantized, kvh, hpb, hd):
    def kernel(*refs):
        if quantized:
            n_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref = refs
        else:
            n_ref, q_ref, k_ref, v_ref, o_ref = refs
        q = q_ref[0, 0].astype(jnp.float32)                  # (R, L)
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
        n_valid = n_ref[0, 0, 0]
        n_blocks = (n_valid + block_kv - 1) // block_kv
        if quantized:
            sel = _lane_selector(kvh, hpb, hd, pl.program_id(1))

        def body(kj, carry):
            start = pl.multiple_of(kj * block_kv, block_kv)
            sl = pl.ds(start, block_kv)
            if quantized:
                k = _dequant(k_ref[0, sl, :], ks_ref[0, sl, :], sel)
                v = _dequant(v_ref[0, sl, :], vs_ref[0, sl, :], sel)
            else:
                k = k_ref[0, sl, :].astype(jnp.float32)      # (bkv, L)
                v = v_ref[0, sl, :].astype(jnp.float32)
            return _attend(q, k, v, start, n_valid, carry,
                           softcap=softcap, scale=scale)

        r, lw = q.shape
        init = (jnp.zeros((r, lw), jnp.float32),
                jnp.full((r, 1), NEG_INF, jnp.float32),
                jnp.zeros((r, 1), jnp.float32))
        acc, _, l = jax.lax.fori_loop(0, n_blocks, body, init)
        o_ref[0, 0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("block_kv", "softcap", "interpret")
)
def flash_decode_kernel(
    q: jax.Array,                        # (B, KV, G, hd)
    k: jax.Array,                        # (B, C, KV*hd) or (B, C, KV, hd)
    v: jax.Array,
    k_scale: Optional[jax.Array],        # (B, C, KV) or None
    v_scale: Optional[jax.Array],
    n_valid: jax.Array,                  # (B, 1) int32
    *,
    block_kv: int = 64,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, kvh, g, hd = q.shape
    c = k.shape[1]
    assert c % block_kv == 0, (c, block_kv)
    quantized = k_scale is not None
    hpb = _heads_per_block(kvh, hd)
    nhb, r, lw = kvh // hpb, hpb * g, hpb * hd
    in_specs = [
        pl.BlockSpec((1, 1, 1), lambda i, hb: (i, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, r, lw), lambda i, hb: (i, hb, 0, 0)),
        pl.BlockSpec((1, c, lw), lambda i, hb: (i, 0, hb)),
        pl.BlockSpec((1, c, lw), lambda i, hb: (i, 0, hb)),
    ]
    args = [jnp.asarray(n_valid, jnp.int32).reshape(b, 1, 1), _expand_q(q, hpb),
            k.reshape(b, c, kvh * hd), v.reshape(b, c, kvh * hd)]
    if quantized:
        in_specs += [pl.BlockSpec((1, c, kvh), lambda i, hb: (i, 0, 0))] * 2
        args += [k_scale, v_scale]
    out = pl.pallas_call(
        _make_kernel(block_kv=block_kv, softcap=softcap, quantized=quantized,
                     kvh=kvh, hpb=hpb, hd=hd),
        grid=(b, nhb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, r, lw), lambda i, hb: (i, hb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nhb, r, lw), q.dtype),
        interpret=pallas_interpret(interpret),
        name="flash_decode_kernel",
    )(*args)
    return _collapse_out(out, hpb, g, hd)


# ---------------------------------------------------------------------------
# Paged variant: block-table walk with scalar-prefetch (SMEM) metadata
# ---------------------------------------------------------------------------
#
# Same online-softmax arithmetic, different iteration structure: the KV
# walk moves from a fori_loop inside one grid step to the (sequential,
# minor) grid dimension j, because with a PrefetchScalarGridSpec it is the
# *index map* — evaluated from SMEM-resident scalars before the DMA — that
# picks which physical block to deliver.  Each step delivers one whole
# pool block across all heads, ``(block_size, KV*hd)``, and walks the head
# blocks with static 128-aligned lane slices; softmax state for every head
# block persists across j in VMEM scratch.  The index map clamps j to the
# last valid block, so steps past the valid prefix repeat the previous
# block index and Pallas issues no DMA for them: the paged kernel reads
# O(valid) bytes.  ``pl.when`` guards init (j == 0), the masked walk
# (j * block_size < n_valid) and the final normalize/write (last j).


def _make_paged_kernel(*, block_size, softcap, quantized, kvh, hpb, hd):
    nhb, lw = kvh // hpb, hpb * hd

    def kernel(*refs):
        if quantized:
            (nv_ref, bt_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             acc_ref, m_ref, l_ref) = refs
        else:
            (nv_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
             acc_ref, m_ref, l_ref) = refs
        del bt_ref  # consumed by the index maps, not the body
        i = pl.program_id(0)
        j = pl.program_id(1)
        n_valid = nv_ref[i]

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(j * block_size < n_valid)
        def _block():
            scale = 1.0 / jnp.sqrt(jnp.float32(hd))
            for hb in range(nhb):
                lanes = slice(hb * lw, (hb + 1) * lw)
                if quantized:
                    sel = _lane_selector(kvh, hpb, hd, hb)
                    k = _dequant(k_ref[0, :, lanes], ks_ref[0], sel)
                    v = _dequant(v_ref[0, :, lanes], vs_ref[0], sel)
                else:
                    k = k_ref[0, :, lanes].astype(jnp.float32)
                    v = v_ref[0, :, lanes].astype(jnp.float32)
                acc, m, l = _attend(
                    q_ref[0, hb].astype(jnp.float32), k, v,
                    j * block_size, n_valid,
                    (acc_ref[hb], m_ref[hb], l_ref[hb]),
                    softcap=softcap, scale=scale,
                )
                acc_ref[hb] = acc
                m_ref[hb] = m
                l_ref[hb] = l

        @pl.when(j == pl.num_programs(1) - 1)
        def _finish():
            o_ref[0] = (
                acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
            ).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("block_size", "softcap", "interpret")
)
def paged_flash_decode_kernel(
    q: jax.Array,                        # (B, KV, G, hd)
    k: jax.Array,                        # (N, bs, KV*hd) or (N, bs, KV, hd)
    v: jax.Array,
    k_scale: Optional[jax.Array],        # (N, bs, KV) or None
    v_scale: Optional[jax.Array],
    block_table: jax.Array,              # (B, J) int32 physical block ids
    n_valid: jax.Array,                  # (B,) int32
    *,
    block_size: int,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, kvh, g, hd = q.shape
    n_phys, bs = k.shape[:2]
    assert bs == block_size, (bs, block_size)
    j_l = block_table.shape[1]
    quantized = k_scale is not None
    hpb = _heads_per_block(kvh, hd)
    nhb, r, lw = kvh // hpb, hpb * g, hpb * hd

    def block_at(i, j, nv, bt):
        last = jnp.maximum(nv[i] - 1, 0) // block_size
        return bt[i, jnp.minimum(j, last)]

    kv_map = lambda i, j, nv, bt: (block_at(i, j, nv, bt), 0, 0)
    per_req = lambda i, j, nv, bt: (i, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, nhb, r, lw), per_req),
        pl.BlockSpec((1, bs, kvh * hd), kv_map),
        pl.BlockSpec((1, bs, kvh * hd), kv_map),
    ]
    args = [_expand_q(q, hpb), k.reshape(n_phys, bs, kvh * hd),
            v.reshape(n_phys, bs, kvh * hd)]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, kvh), kv_map)] * 2
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, j_l),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nhb, r, lw), per_req),
        scratch_shapes=[
            pltpu.VMEM((nhb, r, lw), jnp.float32),           # acc
            pltpu.VMEM((nhb, r, 1), jnp.float32),            # m
            pltpu.VMEM((nhb, r, 1), jnp.float32),            # l
        ],
    )
    out = pl.pallas_call(
        _make_paged_kernel(block_size=block_size, softcap=softcap,
                           quantized=quantized, kvh=kvh, hpb=hpb, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nhb, r, lw), q.dtype),
        interpret=pallas_interpret(interpret),
        name="paged_flash_decode_kernel",
    )(jnp.asarray(n_valid, jnp.int32), jnp.asarray(block_table, jnp.int32),
      *args)
    return _collapse_out(out, hpb, g, hd)
