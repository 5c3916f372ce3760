"""Decode-state containers (KV caches, conv/SSM states, xLSTM states).

Layout mirrors the transformer stack: an (optional) list of per-prologue-layer
states plus, for each position ``j`` in the repeating unit pattern, a state
pytree stacked over the ``U`` scan units (leading axis U).  Windowed attention
layers allocate ``min(max_seq, window)`` rotating slots — this is what makes
gemma3-style 5:1 local:global long-context decode cheap.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention, mamba, xlstm
from repro.models.common import dtype_of


def _layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int, max_seq: int, dtype):
    if spec.kind == "attn":
        length = attention.cache_len(spec, max_seq)
        return attention.init_kv_cache(
            batch, length, cfg.num_kv_heads, cfg.resolved_head_dim, dtype,
            kv_cache_dtype=cfg.kv_cache_dtype,
        )
    if spec.kind == "mamba":
        return mamba.init_mamba_cache(batch, cfg, dtype)
    if spec.kind == "mlstm":
        return xlstm.init_mlstm_cache(batch, cfg)
    if spec.kind == "slstm":
        return xlstm.init_slstm_cache(batch, cfg)
    raise ValueError(spec.kind)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    """Build the full decode state for a model."""
    dtype = dtype_of(cfg.dtype)
    u = cfg.resolved_num_units
    prologue = [
        _layer_cache(spec, cfg, batch, max_seq, dtype) for spec in cfg.prologue
    ]
    units: List[Any] = []
    for spec in cfg.unit_pattern:
        one = _layer_cache(spec, cfg, batch, max_seq, dtype)
        units.append(
            jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (u,) + a.shape), one
            )
        )
    return {"prologue": prologue, "units": units}


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    """ShapeDtypeStruct skeleton of the cache (for dry-run input_specs)."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_seq))


# ---------------------------------------------------------------------------
# Slot pools (continuous-batching serve engine)
# ---------------------------------------------------------------------------
#
# A slot pool is ``n_slots`` independent batch-1 decode states stacked on a
# new leading slot axis: leaf shapes are ``(n_slots,) + leaf(batch=1)``.
# The engine's fused decode step vmaps the per-token serve step over that
# axis (per-slot cache index / RNG key / link round), and admission writes
# a freshly prefilled batch-1 cache into one slot with
# ``jax.lax.dynamic_update_slice`` — both are fixed-shape programs, so
# requests join and retire without retracing.


def init_slot_pool(
    cfg: ModelConfig, n_slots: int, max_seq: int, device=None
) -> Dict[str, Any]:
    """Zeros-initialized pool of ``n_slots`` batch-1 decode states.

    ``device`` places the fresh pool on one specific device as COMMITTED
    arrays — the sharded serving router builds one pool per mesh device,
    and committed state is what keeps every later donated dispatch pinned
    to that shard instead of following the default device."""
    one = cache_spec(cfg, 1, max_seq)
    pool = jax.tree_util.tree_map(
        lambda s: jnp.zeros((n_slots,) + tuple(s.shape), s.dtype), one
    )
    return pool if device is None else jax.device_put(pool, device)


def write_slot(pool: Dict[str, Any], slot_cache: Dict[str, Any], slot) -> Dict[str, Any]:
    """Overwrite slot ``slot`` (a traced int32 scalar is fine) of the pool
    with a batch-1 cache of the same ``max_seq`` — the full-slot reset the
    bucketed prefill performs at admission.  Every leaf of the slot's old
    state is replaced, which is what makes the decode step's dirty writes
    by retired slots harmless."""

    def upd(p, c):
        if c.dtype != p.dtype:
            raise ValueError(
                f"write_slot: cache leaf dtype {c.dtype} does not match pool "
                f"leaf dtype {p.dtype} — a silent cast here would corrupt "
                "quantized caches (e.g. bf16 values written as int8 codes); "
                "build the slot cache from the same config as the pool"
            )
        return jax.lax.dynamic_update_slice(
            p, c[None], (slot,) + (0,) * c.ndim
        )

    return jax.tree_util.tree_map(upd, pool, slot_cache)


def read_slot(pool: Dict[str, Any], slot) -> Dict[str, Any]:
    """One slot's batch-1 cache (dynamic_slice; ``slot`` may be traced)."""

    def rd(p):
        sizes = (1,) + tuple(p.shape[1:])
        out = jax.lax.dynamic_slice(p, (slot,) + (0,) * (p.ndim - 1), sizes)
        return out[0]

    return jax.tree_util.tree_map(rd, pool)


# ---------------------------------------------------------------------------
# Block pools (paged KV storage, vLLM-style)
# ---------------------------------------------------------------------------
#
# A block pool replaces the contiguous per-slot cache with ``num_blocks``
# physical blocks of ``block_size`` KV rows each, shared by every slot.
# Per attention layer the leaves are ``(num_blocks, block_size, KV, hd)``
# codes (+ ``(num_blocks, block_size, KV)`` scales for int8), mirroring the
# cache tree layout ({"prologue": [...], "units": [(U, ...) stacked]}).
#
# Block 0 is RESERVED as the trash block: it is never handed out by the
# host allocator, and the paged decode write routes dead slots' rows there
# (``phys = where(live, table_entry, 0)``) so a retired slot can never
# scribble on a block that has been reallocated to a live request.
#
# Slots own blocks through a per-slot block-table row (engine state,
# ``(max_slots, ceil(max_seq / block_size))`` int32, zero-padded); the
# allocator itself is plain host-side Python in the serve engine — only
# the table crosses into the compiled programs.


def blocks_for(rows: int, block_size: int) -> int:
    """Blocks needed to hold ``rows`` KV rows (ceil division)."""
    return -(-rows // block_size)


def init_block_pool(
    cfg: ModelConfig, num_blocks: int, block_size: int, device=None
) -> Dict[str, Any]:
    """Zeros-initialized global block pool for an attention-only stack.

    Every layer shares the same physical blocks (one pool tree, per-layer
    leaves), so a slot's block-table row addresses all layers at once.
    Windowed layers simply stop using rows past their ``cache_len`` — the
    rotating write index wraps at the layer's own length, and the padded
    tail rows of the last block are inert (never written, and the
    ``k_pos < n_valid`` mask keeps them out of every softmax).

    ``device=`` commits the pool to one device (sharded serving builds one
    pool per shard; committed arrays keep every donated dispatch on that
    shard).
    """
    for spec in cfg.all_layers():
        if spec.kind != "attn":
            raise ValueError(
                "init_block_pool: paged pools support attention-only stacks; "
                f"layer kind {spec.kind!r} carries O(1) recurrent state per "
                "slot and has nothing to page — keep it on the contiguous "
                "slot pool"
            )
    if num_blocks < 2:
        raise ValueError(
            f"init_block_pool: num_blocks={num_blocks} < 2 — block 0 is the "
            "reserved trash block, so a usable pool needs at least one more"
        )
    dtype = dtype_of(cfg.dtype)
    u = cfg.resolved_num_units

    def one():
        return attention.init_kv_cache(
            num_blocks, block_size, cfg.num_kv_heads, cfg.resolved_head_dim,
            dtype, kv_cache_dtype=cfg.kv_cache_dtype,
        )

    prologue = [one() for _ in cfg.prologue]
    # Units get real zero buffers (not broadcast views): the pool is
    # long-lived, donated engine state.
    one_spec = jax.eval_shape(one)
    units: List[Any] = [
        jax.tree_util.tree_map(
            lambda s: jnp.zeros((u,) + tuple(s.shape), s.dtype), one_spec
        )
        for _ in cfg.unit_pattern
    ]
    pool = {"prologue": prologue, "units": units}
    return pool if device is None else jax.device_put(pool, device)


def block_pool_spec(cfg: ModelConfig, num_blocks: int, block_size: int):
    """ShapeDtypeStruct skeleton of the block pool."""
    return jax.eval_shape(lambda: init_block_pool(cfg, num_blocks, block_size))


def write_prompt_blocks(
    pool: Dict[str, Any],
    slot_cache: Dict[str, Any],
    bt_row,
    n_prompt_blocks: int,
    block_size: int,
) -> Dict[str, Any]:
    """Admission copy for the paged pool: scatter only the prompt's blocks.

    ``slot_cache`` is the freshly prefilled batch-1 contiguous cache and
    ``bt_row`` the slot's (zero-padded) block-table row.  Unlike
    :func:`write_slot`, which copies all ``max_seq`` rows of every layer,
    this writes exactly ``n_prompt_blocks`` blocks (``ceil(bucket /
    block_size)``, a static per-bucket constant — padded prompt rows ride
    along exactly as they do in the contiguous slot copy, and stay
    invisible behind the causal mask / ``n_valid``).  If the bucket needs
    more blocks than a short layer (windowed ``cache_len``) or the
    reservation provides, the surplus scatter lands in trash block 0 via
    the table row's zero padding — harmless by construction.
    """

    def upd(p, c):
        if c.dtype != p.dtype:
            raise ValueError(
                f"write_prompt_blocks: cache leaf dtype {c.dtype} does not "
                f"match pool leaf dtype {p.dtype} — build the slot cache "
                "from the same config as the pool"
            )
        rows = c.shape[1]
        j_l = blocks_for(rows, block_size)
        nb = min(n_prompt_blocks, j_l)
        flat = c[0]
        pad = j_l * block_size - rows
        if pad:
            flat = jnp.pad(flat, ((0, pad),) + ((0, 0),) * (flat.ndim - 1))
        blocks = flat.reshape((j_l, block_size) + flat.shape[1:])[:nb]
        return p.at[bt_row[:nb]].set(blocks)

    prologue = [
        jax.tree_util.tree_map(upd, p, c)
        for p, c in zip(pool["prologue"], slot_cache["prologue"])
    ]
    units = [
        jax.tree_util.tree_map(lambda pp, cc: jax.vmap(upd)(pp, cc), pu, cu)
        for pu, cu in zip(pool["units"], slot_cache["units"])
    ]
    return {"prologue": prologue, "units": units}


def _kv_itemsize(cfg: ModelConfig) -> int:
    if cfg.kv_cache_dtype == "int8":
        return 1
    return jnp.dtype(dtype_of(cfg.dtype)).itemsize


def _attn_row_bytes(cfg: ModelConfig) -> int:
    """Bytes per KV row of one attention layer (k + v codes, plus bf16
    scales when the cache is int8-quantized)."""
    row_bytes = 2 * cfg.num_kv_heads * cfg.resolved_head_dim * _kv_itemsize(cfg)
    if cfg.kv_cache_dtype == "int8":
        row_bytes += 2 * cfg.num_kv_heads * 2
    return row_bytes


def _decode_block(cfg: ModelConfig, length: int) -> int:
    """KV rows per block the contiguous decode kernel walks in one layer
    of ``length`` cache rows."""
    from repro.kernels.decode_attention import decode_block_kv

    lanes = cfg.num_kv_heads * cfg.resolved_head_dim
    return decode_block_kv(length, lanes, _kv_itemsize(cfg))


def decode_read_bytes(
    cfg: ModelConfig,
    max_seq: int,
    valid: int,
    masked: bool = True,
    paged: bool = False,
    block_size: int = 16,
) -> int:
    """Attention-cache bytes ONE decode step reads for one request.

    ``masked=False`` is the legacy full-cache path: every attention layer
    reads (and for int8, dequantizes) all ``cache_len`` K/V rows + scales.
    ``masked=True`` is the length-masked flash-decode path: the kernel's
    index maps stop at the last valid block, so only
    ``ceil(valid / decode_block_kv(...))`` blocks of the layer's panel are
    DMA'd — the block size the kernel itself takes from the cache's shape.
    ``paged=True`` is the block-table path: ``ceil(valid / block_size)``
    pool blocks of KV rows per layer, plus the scalar-prefetch metadata
    the kernel stages through SMEM (the int32 block-table row and the
    int32 ``n_valid`` scalar; see kernels/README.md).  Analytic — no
    allocation; ``benchmarks/decode_attn_bench.py`` reports it next to the
    measured step latency.
    """
    import math

    row_bytes = _attn_row_bytes(cfg)
    total = 0
    for spec in cfg.all_layers():
        if spec.kind != "attn":
            continue
        length = attention.cache_len(spec, max_seq)
        if paged:
            j_l = blocks_for(length, block_size)
            nblk = min(math.ceil(min(valid, length) / block_size), j_l)
            rows = nblk * block_size
            total += rows * row_bytes + 4 * j_l + 4     # + bt row + n_valid
            continue
        if masked:
            bkv = _decode_block(cfg, length)
            rows = min(math.ceil(min(valid, length) / bkv) * bkv, length)
        else:
            rows = length
        total += rows * row_bytes
    return total


def decode_read_bytes_jnp(
    cfg: ModelConfig,
    max_seq: int,
    valid,
    masked: bool = True,
    paged: bool = False,
    block_size: int = 16,
):
    """Traced twin of :func:`decode_read_bytes`: ``valid`` may be a traced
    scalar or vector (the slot pool's per-slot lengths), so the slot-pool
    engine can accumulate the per-step read-bytes device counter inside
    the fused decode program.  Agrees exactly with the int analytic for
    every concrete ``valid`` (tested) — the per-layer cache lengths and
    effective block sizes are static, only the ceil-to-block arithmetic
    runs on device."""
    row_bytes = _attn_row_bytes(cfg)
    valid = jnp.asarray(valid, jnp.float32)
    total = jnp.zeros_like(valid)
    for spec in cfg.all_layers():
        if spec.kind != "attn":
            continue
        length = attention.cache_len(spec, max_seq)
        if paged:
            j_l = blocks_for(length, block_size)
            v = jnp.minimum(valid, float(length))
            nblk = jnp.minimum(jnp.ceil(v / block_size), float(j_l))
            total = total + (
                nblk * float(block_size * row_bytes) + float(4 * j_l + 4)
            )
            continue
        if masked:
            bkv = _decode_block(cfg, length)
            v = jnp.minimum(valid, float(length))
            rows = jnp.minimum(jnp.ceil(v / bkv) * bkv, float(length))
        else:
            rows = jnp.full_like(valid, float(length))
        total = total + rows * float(row_bytes)
    return total


def admission_write_bytes(
    cfg: ModelConfig,
    max_seq: int,
    bucket: int,
    paged: bool = False,
    block_size: int = 16,
) -> int:
    """Cache bytes ONE admission writes into the pool for one request.

    Contiguous slot pool: :func:`write_slot` replaces every leaf of the
    slot — the full batch-1 ``max_seq`` cache, independent of the prompt.
    Paged pool: :func:`write_prompt_blocks` scatters only
    ``ceil(bucket / block_size)`` blocks per layer (capped at the layer's
    own block count), so the copy scales with the padded prompt length,
    not ``max_seq``.
    """
    if not paged:
        return cache_bytes(cfg, 1, max_seq)
    row_bytes = _attn_row_bytes(cfg)
    total = 0
    for spec in cfg.all_layers():
        if spec.kind != "attn":
            continue
        length = attention.cache_len(spec, max_seq)
        nb = min(blocks_for(bucket, block_size), blocks_for(length, block_size))
        total += nb * block_size * row_bytes
    return total


def block_pool_bytes(cfg: ModelConfig, num_blocks: int, block_size: int) -> int:
    """Total block-pool footprint in bytes (no allocation) — the paged
    counterpart of :func:`cache_bytes`, used to size equal-HBM
    comparisons in ``benchmarks/serving_bench.py``."""
    import math

    return sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(
            block_pool_spec(cfg, num_blocks, block_size)
        )
    )


def cache_bytes(cfg: ModelConfig, batch: int, max_seq: int) -> int:
    """Total decode-state footprint in bytes (no allocation) — what the
    serve engine's donated-cache scan carries, reported by decode_bench."""
    import math

    return sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(cache_spec(cfg, batch, max_seq))
    )
