"""The split stack with a cache: two loops over absolute unit indices of the
whole stacked weights, carrying the whole stacked caches.

* ``run_stack`` with a cache (a prefill, then decode steps) gives the
  logits and caches, bit for bit, of a plain loop that slices unit ``l``'s
  weights and cache, runs its layers and stacks the caches back; its
  prefill logits are those of the forward without a cache.
* The serving engines give the greedy tokens of ``generate_reference``
  with the link before, inside and after the stack.
* The compiled decode step of the contiguous slot pool holds no
  concatenate, and no slice or copy of a stacked cache or weight segment,
  and its cache output aliases the donated pool.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES
from repro.launch.serve import generate_reference
from repro.models import cache as cache_lib, lm, transformer
from repro.serve import ContinuousEngine, PoolConfig

UNITS = 3
SPLITS = [0, 1, UNITS - 1, UNITS]


def _qwen(split, kv_cache_dtype=""):
    cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced(
        num_units=UNITS, num_layers=UNITS, kv_cache_dtype=kv_cache_dtype
    )
    return cfg.with_updates(link=dataclasses.replace(
        cfg.link, split_after_units=split, loss_rate=0.3, channel="ge"
    ))


def _reference_stack(params, x, cfg, positions, cache, cache_index, link_fn):
    """The stack as a plain per-unit loop: unit ``l``'s weights and cache
    sliced out, its layers run, the units' caches stacked back."""
    u = cfg.resolved_num_units
    split = min(max(cfg.link.split_after_units, 0), u)
    pro = []
    for i, spec in enumerate(cfg.prologue):
        x, c, _ = transformer.layer_forward(
            params["prologue"][i], x, cfg, spec, positions,
            cache["prologue"][i], cache_index,
        )
        pro.append(c)
    at = lambda tree, l: jax.tree_util.tree_map(lambda a: a[l], tree)
    units = []
    for l in range(u):
        if l == split:
            x = link_fn(x)
        new = []
        for j, spec in enumerate(cfg.unit_pattern):
            x, c, _ = transformer.layer_forward(
                at(params["units"], l)[j], x, cfg, spec, positions,
                at(cache["units"][j], l), cache_index,
            )
            new.append(c)
        units.append(new)
    if split == u:
        x = link_fn(x)
    stacked = [
        jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[n[j] for n in units])
        for j in range(len(cfg.unit_pattern))
    ]
    return x, {"prologue": pro, "units": stacked}


def _assert_same(got, want, rtol):
    check = (
        np.testing.assert_array_equal if rtol == 0 else
        lambda a, b: np.testing.assert_allclose(a, b, rtol=rtol, atol=rtol)
    )
    jax.tree_util.tree_map(
        lambda a, b: check(np.asarray(a), np.asarray(b)), got, want
    )


def _check_stack(cfg, batch=2, prompt=6, steps=2, rtol=0.0):
    """Prefill, then ``steps`` decode steps, through ``run_stack`` and the
    plain loop: activations and caches equal at every step (bit for bit
    unless ``rtol`` is given)."""
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    stack = params["stack"]
    link_fn = lm.make_link_fn(cfg, params["link"], jax.random.PRNGKey(5), "serve")

    def carried(x, positions, cache, index):
        out, new, _ = transformer.run_stack(
            stack, x, cfg, positions, cache=cache, cache_index=index,
            link_fn=link_fn, mode="decode",
        )
        return out, new

    def plain(x, positions, cache, index):
        return _reference_stack(stack, x, cfg, positions, cache, index, link_fn)

    carried, plain = jax.jit(carried), jax.jit(plain)
    cache = cache_lib.init_cache(cfg, batch, prompt + steps)
    keys = jax.random.split(jax.random.PRNGKey(1), steps + 1)
    x = jax.random.normal(keys[0], (batch, prompt, cfg.d_model), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(prompt), (batch, prompt))
    index = jnp.int32(0)
    for i in range(steps + 1):
        got = carried(x, positions, cache, index)
        want = plain(x, positions, cache, index)
        _assert_same(got, want, rtol)
        cache = got[1]
        index = jnp.int32(prompt + i)
        x = jax.random.normal(keys[i], (batch, 1, cfg.d_model), jnp.float32)
        positions = jnp.full((batch, 1), prompt + i, jnp.int32)


def _tokens_match(cfg, pool, lengths, tokens, key):
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(cfg, pool)
    reqs = [
        eng.submit(np.arange(1, n + 1, dtype=np.int32) * (i + 3) % cfg.vocab_size,
                   tokens, key=jax.random.fold_in(key, i))
        for i, n in enumerate(lengths)
    ]
    eng.run(params)
    for i, req in enumerate(reqs):
        ref, _ = generate_reference(
            params, cfg, jnp.asarray(req.prompt)[None], tokens,
            key=jax.random.fold_in(key, i),
        )
        np.testing.assert_array_equal(np.asarray(ref)[0], req.tokens)
    return eng


def _stacked_shapes(tree, lead, min_ndim=0):
    """Shapes a slice or copy of a segment of ``tree``'s stacked leaves
    would have: leading unit axis of 2 or more, at ``lead`` (0 for the
    weights, 1 for the slot pool's caches)."""
    out = set()
    for a in jax.tree_util.tree_leaves(tree):
        if a.ndim < min_ndim:
            continue
        u = a.shape[lead]
        for n in range(2, u + 1):
            out.add(a.shape[:lead] + (n,) + a.shape[lead + 1:])
    return out


def _hlo_shape(dims):
    return tuple(int(d) for d in dims.split(",") if d)


@pytest.mark.parametrize("kv_cache_dtype", ["", "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("split", SPLITS)
class TestCarriedStack:
    def test_matches_plain_loop(self, split, kv_cache_dtype):
        _check_stack(_qwen(split, kv_cache_dtype))

    def test_prefill_logits_match_forward_without_cache(self, split, kv_cache_dtype):
        cfg = _qwen(split, kv_cache_dtype)
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        tokens = jnp.arange(1, 8, dtype=jnp.int32)[None] % cfg.vocab_size
        key = jax.random.PRNGKey(2)
        run = jax.jit(lambda p, t, c: lm.forward(
            p, t, cfg, cache=c, cache_index=0, link_key=key,
            link_mode="serve", mode="prefill",
        )[0])
        plain = jax.jit(lambda p, t: lm.forward(
            p, t, cfg, link_key=key, link_mode="serve", mode="prefill",
        )[0])
        np.testing.assert_array_equal(
            np.asarray(run(params, tokens, cache_lib.init_cache(cfg, 1, 12))),
            np.asarray(plain(params, tokens)),
        )

    def test_engine_tokens_and_decode_program(self, split, kv_cache_dtype):
        """Greedy tokens equal ``generate_reference``; the decode program
        copies no stacked segment and updates the donated pool in place."""
        cfg = _qwen(split, kv_cache_dtype)
        eng = _tokens_match(
            cfg, PoolConfig(max_slots=2, max_new=4, max_prompt=8),
            [6, 6], 4, jax.random.PRNGKey(11),
        )
        text = eng.decode_executable.as_text()
        params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
        state = jax.eval_shape(eng._init_state)
        # Weight matrices (vectors' segments share shapes with small
        # activations) and the pool's caches.
        banned = (_stacked_shapes(params["stack"]["units"], 0, min_ndim=3)
                  | _stacked_shapes(state["cache"]["units"], 1))
        ops = re.findall(
            r"\[([\d,]*)\]\{[^}]*\} (concatenate|slice|copy)\(", text
        )
        assert ops
        for dims, op in ops:
            assert _hlo_shape(dims) not in banned, (op, dims)
        # Outputs are the state's leaves in order; inputs are the params'
        # leaves, then the state's.  Each cache leaf's output aliases its
        # own input.
        n_params = len(jax.tree_util.tree_leaves(params))
        leaves = jax.tree_util.tree_leaves_with_path(state)
        aliases = dict(
            (int(o), int(i)) for o, i in
            re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", text)
        )
        cache_leaves = [n for n, (path, _) in enumerate(leaves)
                        if "cache" in jax.tree_util.keystr(path)]
        assert cache_leaves
        for n in cache_leaves:
            assert aliases.get(n) == n_params + n, (n, aliases)


@pytest.mark.parametrize("arch, units, rtol", [("xlstm-350m", 3, 1e-5),
                                               ("jamba-v0.1-52b", 2, 0.0)])
def test_recurrent_units_write_back_their_state(arch, units, rtol):
    """Recurrent units (mLSTM/sLSTM, Mamba beside attention), with the link
    after the first unit, write their whole state back into the carried
    stack at their unit.  XLA on the CPU
    fuses the mLSTM's chunked scan differently inside the unit loop than
    in the unrolled plain loop, so xLSTM agrees to float32 rounding."""
    cfg = ARCHITECTURES[arch].reduced(num_units=units)
    cfg = cfg.with_updates(
        num_layers=len(cfg.prologue) + units * len(cfg.unit_pattern),
        link=dataclasses.replace(cfg.link, split_after_units=1, loss_rate=0.3),
    )
    _check_stack(cfg, prompt=4, steps=1, rtol=rtol)


def test_paged_pool_split_inside_the_stack():
    """The paged pool with the link between units 1 and 2 of 3: the
    block-table writes land in the carried stack at their unit."""
    cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced(
        num_units=3, num_layers=3, attn_impl="flash_decode",
        kv_cache_dtype="int8",
    )
    cfg = cfg.with_updates(link=dataclasses.replace(
        cfg.link, split_after_units=1, loss_rate=0.3, channel="ge"
    ))
    _tokens_match(
        cfg, PoolConfig(max_slots=2, max_new=4, max_prompt=8,
                        paged=True, block_size=4),
        [6, 6], 4, jax.random.PRNGKey(13),
    )
