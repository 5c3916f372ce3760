"""Flash-decode forward kernels (Pallas): length-masked online-softmax
attention for the s == 1 decode step, with inline int8 dequantization.

Each wrapper reads the model's cache layout as it is, lane-dense:

* q        — (B, KV, G, hd)   one query token, GQA-grouped
* k / v    — (B, C, KV*hd)    one layer's rotating cache buffer (int8 codes
                              or bf16), one row of KV*hd lanes per position
                              (a (B, C, KV, hd) buffer is reshaped to it),
             or (U, B, C, KV*hd) the layer's cache stacked over the stack's
                              units, read at unit ``unit`` in place
* k/v scale— (B, C, KV)       per-(pos, head) bf16 absmax scales (int8 only),
             or (U, B, C, KV) stacked like k / v
* n_valid  — (B,) int32       count of live cache slots for this request

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

Both kernels walk KV blocks on the minor grid axis with one body
(:func:`_make_walk_kernel`): grid (requests, blocks), each step delivers
one ``(block, KV*hd)`` K and V block across all heads (plus its
``(block, KV)`` scales), walks the head blocks with static 128-aligned
lane slices, and keeps the softmax state of every head block in VMEM
scratch across the sequential block axis.  The index maps read
``n_valid`` from SMEM (scalar prefetch) and clamp the block position to
the request's last valid block, so steps past ``ceil(n_valid / block)``
repeat the previous block index: Pallas issues no DMA for them and the
body skips them.  Both kernels read O(valid) bytes.

* **Contiguous** (``flash_decode_kernel``): the cache is a pool of
  *panels*, ``(N, C, KV*hd)`` — a stacked ``(U, B, C, KV*hd)`` cache is
  the pool of its ``U*B`` panels by a free reshape — and a second
  scalar-prefetch vector names each request's panel (``unit*B + b``).  So
  the kernel reads unit ``unit`` of a stacked cache where it lies; nothing
  copies the layer out first.  Its batching rule (``custom_vmap``) folds a
  vmapped axis into the request axis and offsets the panel indices, so
  the slot-pool engine's vmap binds ONE ``pallas_call`` over all slots
  per layer, with the per-slot ``n_valid``, never a loop of per-slot
  calls (which is what ``pallas_call`` does with a batched scalar-prefetch
  operand).  The block size comes from the cache's shape alone
  (:func:`decode_block_kv`).
* **Paged** (``paged_flash_decode_kernel``): the walk follows a
  per-request block table over a shared ``(num_blocks, block_size,
  KV*hd)`` pool; the table is the second scalar-prefetch operand.

Rotating sliding-window caches need no extra handling: writes land at
``index % C`` (``models.attention._write_decode``), so the live slots are
always the contiguous prefix ``[0, min(index + 1, C))``.  Cached keys carry
RoPE from write time and softmax is permutation-invariant over slots.

``ref.py`` runs the same f32 arithmetic per head, so the jnp fallback
agrees with the interpret-mode kernels to float-ulp level (XLA summation
order is the only difference).
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
# Bytes of one K (or V) block the contiguous walk aims for: large enough
# that a grid step's fixed cost is small beside its DMA, small enough that
# a slot's last, partly valid block over-reads little.
BLOCK_BYTES = 128 * 1024


def decode_block_kv(cache_len: int, lanes: int, itemsize: int) -> int:
    """KV rows per block of the decode walk, from the cache's shape alone:
    ``cache_len`` rows of ``lanes`` elements of ``itemsize`` bytes.

    Aims at :data:`BLOCK_BYTES` per K block (64 rows of 1024 bf16 lanes,
    128 rows of 512).  A cache that fits in one such block is one block
    (a block spanning the whole dim is always legal).  Otherwise the
    largest divisor of ``cache_len`` under the aim that is a whole number
    of the dtype's packed (8, 128) tiles; when there is none, the aim
    itself, and ``decode_attention`` pads the position axis to a multiple.
    """
    rows = max(BLOCK_BYTES // (lanes * itemsize), 1)
    if cache_len <= rows:
        return cache_len
    tile = 32 // min(itemsize, 4)                 # f32 8, bf16 16, int8 32
    fits = [b for b in range(tile, rows + 1, tile) if cache_len % b == 0]
    return fits[-1] if fits else max(rows // tile, 1) * tile


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


# ---------------------------------------------------------------------------
# The block walk both kernels share
# ---------------------------------------------------------------------------
#
# ``pl.when`` guards init (j == 0), the masked update (j * block <
# n_valid) and the final normalize/write (last j).  The second
# scalar-prefetch operand (panel ids or the block table) is consumed by
# the index maps, not the body.


def _make_walk_kernel(*, block, softcap, quantized, kvh, hpb, hd):
    nhb, lw = kvh // hpb, hpb * hd

    def kernel(*refs):
        if quantized:
            (nv_ref, _, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             acc_ref, m_ref, l_ref) = refs
        else:
            (nv_ref, _, q_ref, k_ref, v_ref, o_ref,
             acc_ref, m_ref, l_ref) = refs
        i = pl.program_id(0)
        j = pl.program_id(1)
        n_valid = nv_ref[i]

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(j * block < n_valid)
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
                    j * block, n_valid,
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


def _walk_call(qx, k, v, scales, n_valid, index, kv_map, *, n_blocks, block,
               softcap, kvh, hpb, hd, name, interpret):
    """One ``pallas_call`` of the block walk over ``n_blocks`` blocks per
    request.  ``qx`` is the block-diagonal query ``(R, nhb, r, lw)``; K/V
    (and the scales) are addressed through ``kv_map(i, j, n_valid, index)``
    with blocks of ``block`` rows across all lanes."""
    n_req, nhb, r, lw = qx.shape
    lanes = k.shape[-1]
    per_req = lambda i, j, nv, ix: (i, 0, 0, 0)
    in_specs = [pl.BlockSpec((1, nhb, r, lw), per_req),
                pl.BlockSpec((1, block, lanes), kv_map),
                pl.BlockSpec((1, block, lanes), kv_map)]
    in_specs += [pl.BlockSpec((1, block, kvh), kv_map)] * len(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_req, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nhb, r, lw), per_req),
        scratch_shapes=[
            pltpu.VMEM((nhb, r, lw), jnp.float32),           # acc
            pltpu.VMEM((nhb, r, 1), jnp.float32),            # m
            pltpu.VMEM((nhb, r, 1), jnp.float32),            # l
        ],
    )
    return pl.pallas_call(
        _make_walk_kernel(block=block, softcap=softcap, quantized=bool(scales),
                          kvh=kvh, hpb=hpb, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_req, nhb, r, lw), qx.dtype),
        interpret=pallas_interpret(interpret),
        name=name,
    )(n_valid, index, qx, k, v, *scales)


def _last_block(n_valid, block):
    return jnp.maximum(n_valid - 1, 0) // block


# ---------------------------------------------------------------------------
# Contiguous kernel: a pool of panels, read in place
# ---------------------------------------------------------------------------


def _panel_walk(block: int, softcap: float, kvh: int, hpb: int, hd: int,
                interpret: Optional[bool]):
    """The contiguous walk as a ``custom_vmap`` function of
    ``(qx (R, nhb, r, lw), k/v (N, C, L), scales, n_valid (R,), panel (R,))``.
    Its batching rule folds the vmapped axis into the request axis (and,
    where the pool is batched, into the panel axis, offsetting the panel
    ids), then calls the function itself: vmapped at any depth it stays
    one ``pallas_call``."""

    @jax.custom_batching.custom_vmap
    def walk(qx, k, v, scales, n_valid, panel):
        block_at = lambda i, j, nv, pn: (
            pn[i], jnp.minimum(j, _last_block(nv[i], block)), 0
        )
        return _walk_call(
            qx, k, v, scales, n_valid, panel, block_at,
            n_blocks=k.shape[1] // block, block=block, softcap=softcap,
            kvh=kvh, hpb=hpb, hd=hd, name="flash_decode_kernel",
            interpret=interpret,
        )

    @walk.def_vmap
    def _fold(size, batched, qx, k, v, scales, n_valid, panel):
        qb, kb, vb, sb, nb, pb = batched
        lead = lambda x, b: x if b else jnp.broadcast_to(x, (size,) + x.shape)
        fold = lambda x: x.reshape((-1,) + x.shape[2:])
        offset = 0
        if kb or vb or any(sb):
            n_panels = k.shape[int(kb)]
            offset = n_panels * jnp.arange(size, dtype=jnp.int32)[:, None]
            k, v = fold(lead(k, kb)), fold(lead(v, vb))
            scales = tuple(fold(lead(s, b)) for s, b in zip(scales, sb))
        out = walk(fold(lead(qx, qb)), k, v, scales, fold(lead(n_valid, nb)),
                   fold(lead(panel, pb) + offset))
        return out.reshape((size, -1) + out.shape[1:]), True

    return walk


@functools.partial(
    jax.jit, static_argnames=("block_kv", "softcap", "interpret")
)
def flash_decode_kernel(
    q: jax.Array,                        # (B, KV, G, hd)
    k: jax.Array,                        # (B, C, KV*hd) | (B, C, KV, hd)
    v: jax.Array,                        #   or (U, B, C, KV*hd) with unit
    k_scale: Optional[jax.Array],        # (B, C, KV) | (U, B, C, KV) | None
    v_scale: Optional[jax.Array],
    n_valid: jax.Array,                  # (B,) or (B, 1) int32
    unit=None,                           # unit of a stacked cache, or None
    *,
    block_kv: Optional[int] = None,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Length-masked decode attention over one layer's cache, or over unit
    ``unit`` of the layer's cache stacked over units, read in place.
    ``block_kv`` defaults to :func:`decode_block_kv` of the cache's shape
    and must divide the cache length."""
    b, kvh, g, hd = q.shape
    lanes = kvh * hd
    if unit is None:                     # one layer: a stack of one unit
        unit = 0
        k, v = k.reshape(1, b, -1, lanes), v.reshape(1, b, -1, lanes)
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    u, c = k.shape[0], k.shape[2]
    bkv = block_kv or decode_block_kv(c, lanes, k.dtype.itemsize)
    assert c % bkv == 0, (c, bkv)
    hpb = _heads_per_block(kvh, hd)
    panels = lambda a: a.reshape((u * b,) + a.shape[2:])
    scales = () if k_scale is None else (panels(k_scale), panels(v_scale))
    panel = jnp.asarray(unit, jnp.int32) * b + jnp.arange(b, dtype=jnp.int32)
    n = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1), (b,))
    out = _panel_walk(bkv, softcap, kvh, hpb, hd, interpret)(
        _expand_q(q, hpb), panels(k), panels(v), scales, n, panel
    )
    return _collapse_out(out, hpb, g, hd)


# ---------------------------------------------------------------------------
# Paged kernel: block-table walk over a shared pool
# ---------------------------------------------------------------------------


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
    hpb = _heads_per_block(kvh, hd)
    rows = lambda a: a.reshape(n_phys, bs, kvh * hd)
    scales = () if k_scale is None else (k_scale, v_scale)
    block_at = lambda i, j, nv, bt: (
        bt[i, jnp.minimum(j, _last_block(nv[i], block_size))], 0, 0
    )
    out = _walk_call(
        _expand_q(q, hpb), rows(k), rows(v), scales,
        jnp.asarray(n_valid, jnp.int32), jnp.asarray(block_table, jnp.int32),
        block_at, n_blocks=block_table.shape[1], block=block_size,
        softcap=softcap, kvh=kvh, hpb=hpb, hd=hd,
        name="paged_flash_decode_kernel", interpret=interpret,
    )
    return _collapse_out(out, hpb, g, hd)
