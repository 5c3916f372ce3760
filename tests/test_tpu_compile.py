"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler that ships with libtpu compiles each
kernel for a chip that is described, not attached, and raises what the
chip's compiler would raise (block shapes off the (8, 128) tiling,
unsupported vector casts, too much VMEM).  Interpret-mode tests cannot
see any of that.  Widths are qwen1.5-0.5b's (16 KV heads, one query per
KV head, head dim 64) and one GQA layout (8 KV heads, 4 queries each,
head dim 128), with bf16 and int8 caches.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and every test worker imports this
file.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (
    flash_decode_kernel,
    paged_flash_decode_kernel,
)
from repro.kernels.lossy_link.kernel import (
    burst_mask_kernel,
    lossy_link_egress_kernel,
)

# (KV heads, queries per KV head, head dim)
WIDTHS = {"qwen1.5-0.5b": (16, 1, 64), "gqa-hd128": (8, 4, 128)}
BATCH, CACHE_LEN, BLOCK_KV = 8, 192, 64
POOL_BLOCKS, BLOCK_SIZE, TABLE_LEN = 8 * 12 + 1, 16, 12


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device sharding, with the persistent compile cache off: a
    described-chip executable is written to the cache but cannot be read
    back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _cache_avals(sharding, lead, width, dtype):
    kvh, _, hd = width
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    kv = [s(lead + (kvh, hd), dtype)] * 2
    scales = [s(lead + (kvh,), jnp.bfloat16)] * 2 if dtype == jnp.int8 else []
    return kv, scales


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_decode_kernel(one_chip, width, dtype):
    kvh, g, hd = WIDTHS[width]
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv, scales = _cache_avals(one_chip, (BATCH, CACHE_LEN), WIDTHS[width], dtype)

    def fn(q, k, v, n, *sc):
        ks, vs = sc or (None, None)
        return flash_decode_kernel(q, k, v, ks, vs, n, block_kv=BLOCK_KV,
                                   interpret=False)

    _compile(fn, s((BATCH, kvh, g, hd), jnp.bfloat16), *kv,
             s((BATCH, 1), jnp.int32), *scales)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_flash_decode_kernel_vmapped_over_slots(one_chip, dtype):
    """The slot-pool engine's form: batch-1 calls vmapped over the slots."""
    kvh, g, hd = WIDTHS["qwen1.5-0.5b"]
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv, scales = _cache_avals(one_chip, (BATCH, CACHE_LEN), WIDTHS["qwen1.5-0.5b"],
                              dtype)

    def one(q, k, v, n, *sc):
        sc = [a[None] for a in sc] or [None, None]
        return flash_decode_kernel(q[None], k[None], v[None], *sc,
                                   n.reshape(1, 1), block_kv=BLOCK_KV,
                                   interpret=False)[0]

    _compile(jax.vmap(one), s((BATCH, kvh, g, hd), jnp.bfloat16), *kv,
             s((BATCH,), jnp.int32), *scales)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_flash_decode_kernel(one_chip, width, dtype):
    kvh, g, hd = WIDTHS[width]
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv, scales = _cache_avals(one_chip, (POOL_BLOCKS, BLOCK_SIZE),
                              WIDTHS[width], dtype)

    def fn(q, k, v, bt, n, *sc):
        ks, vs = sc or (None, None)
        return paged_flash_decode_kernel(q, k, v, ks, vs, bt, n,
                                         block_size=BLOCK_SIZE, interpret=False)

    _compile(fn, s((BATCH, kvh, g, hd), jnp.bfloat16), *kv,
             s((BATCH, TABLE_LEN), jnp.int32), s((BATCH,), jnp.int32), *scales)


def test_burst_mask_kernel(one_chip):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _compile(
        lambda ui, ul, ut: burst_mask_kernel(
            ui, ul, ut, p_gb=0.1, p_bg=0.3, loss_good=0.02, loss_bad=0.8,
            interpret=False,
        ),
        s((64,)), s((64, 100)), s((64, 100)),
    )


def test_lossy_link_egress_kernel(one_chip):
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip
    )
    _compile(
        lambda x, u, lo, hi: lossy_link_egress_kernel(
            x, u, lo, hi, bits=8, loss_rate=0.3, interpret=False
        ),
        s((512, 1024), jnp.bfloat16), s((512, 1024)), s((1024,)), s((1024,)),
    )


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous-bf16", "paged-int8"])
def test_fused_decode_step(one_chip, paged, monkeypatch):
    """The slot-pool engine's whole decode step at qwen1.5-0.5b's widths,
    cut to 2 layers.  The decode-attention dispatch sees the CPU backend
    here, so the test forces the compiled kernel."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import ContinuousEngine, PoolConfig

    monkeypatch.setenv("REPRO_FLASH_DECODE_IMPL", "kernel")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = get_config("qwen1.5-0.5b").with_updates(num_layers=2)
    if paged:
        cfg = cfg.with_updates(kv_cache_dtype="int8")
    eng = ContinuousEngine(cfg, PoolConfig(max_prompt=64, max_new=32, paged=paged))
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = place(jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg)))
    state = place(jax.eval_shape(eng._init_state))
    step = jax.jit(eng._make_decode_step(), donate_argnums=(1,))
    assert "tpu_custom_call" in step.lower(params, state).compile().as_text()


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_decode_step_names_its_kernel(one_chip, paged, monkeypatch):
    """The decode step's lowered program names its Pallas call, with the
    name the profiler shows for the kernel's op (the trace reductions
    match it)."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import ContinuousEngine, PoolConfig

    monkeypatch.setenv("REPRO_FLASH_DECODE_IMPL", "kernel")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = get_config("qwen1.5-0.5b").with_updates(num_layers=2)
    eng = ContinuousEngine(cfg, PoolConfig(max_prompt=64, max_new=32, paged=paged))
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = place(jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg)))
    state = place(jax.eval_shape(eng._init_state))
    text = jax.jit(eng._make_decode_step()).lower(params, state).as_text()
    name = "paged_flash_decode_kernel" if paged else "flash_decode_kernel"
    assert re.findall(r'kernel_name = "(\w+)"', text) == [name]


def test_decode_step_copies_no_stacked_segment(one_chip, monkeypatch):
    """The contiguous decode step at qwen1.5-0.5b's widths, 4 layers with
    the link after the first, compiled for a v5e: no concatenate, slice
    or copy shaped like two or more units of the slot pool or of the
    stacked weight matrices (a relayout of the pool, or a segment cut at
    the split), and the pool's K/V alias the donated input."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import ContinuousEngine, PoolConfig

    monkeypatch.setenv("REPRO_FLASH_DECODE_IMPL", "kernel")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = get_config("qwen1.5-0.5b").with_updates(num_layers=4)
    cfg = cfg.with_updates(link=dataclasses.replace(cfg.link, split_after_units=1))
    eng = ContinuousEngine(cfg, PoolConfig(max_prompt=64, max_new=32))
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = place(jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg)))
    state = place(jax.eval_shape(eng._init_state))
    text = jax.jit(eng._make_decode_step(), donate_argnums=(1,)).lower(
        params, state).compile().as_text()

    def segments(tree, lead, min_ndim):
        return {a.shape[:lead] + (n,) + a.shape[lead + 1:]
                for a in jax.tree_util.tree_leaves(tree) if a.ndim >= min_ndim
                for n in range(2, a.shape[lead] + 1)}

    banned = (segments(params["stack"]["units"], 0, 3)
              | segments(state["cache"]["units"], 1, 0))
    ops = re.findall(r"\[([\d,]*)\]\{[^}]*\} (concatenate|slice|copy)\(", text)
    for dims, op in ops:
        assert tuple(int(d) for d in dims.split(",") if d) not in banned, (op, dims)
    n_params = len(jax.tree_util.tree_leaves(params))
    aliases = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", text))
    leaves = jax.tree_util.tree_leaves_with_path(state)
    cache = [n for n, (path, _) in enumerate(leaves)
             if "cache" in jax.tree_util.keystr(path)]
    assert cache and all(aliases.get(str(n)) == str(n_params + n) for n in cache)
