"""Length-masked flash-decode attention (repro.kernels.decode_attention):

* triangulated equivalence — Pallas kernel (interpret mode) must be
  BIT-IDENTICAL to the pure-jnp ref fallback (same arithmetic, two
  implementations), and both must match the full-cache masked
  ``_naive_attn`` oracle numerically — across GQA group sizes, bf16/int8
  caches, and valid lengths straddling the block boundary;
* rotating sliding-window integration — decode steps through
  ``attention_forward`` across the window wrap point, flash_decode vs the
  naive oracle;
* the naive fallback's concrete-index prefix slice (satellite fix) matches
  the traced masked form;
* ``ContinuousEngine`` with ``attn_impl="flash_decode"``: greedy outputs
  token-identical to ``generate_reference`` under iid + Gilbert-Elliott
  links, and the AOT compile count stays ``num_buckets + 1`` with zero
  steady-state builds.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES
from repro.kernels.decode_attention import (
    decode_attention,
    decode_block_kv,
    flash_decode_kernel,
    flash_decode_ref,
)
from repro.launch.serve import generate_reference
from repro.models import lm
from repro.models.attention import _naive_attn, _read_cache
from repro.serve import ContinuousEngine, PoolConfig

BKV = 8


def _make_qcache(seed, b, c, kvh, g, hd, quantized, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, 1, kvh, g, hd), dtype)
    if quantized:
        cache = {
            "k": jax.random.randint(ks[1], (b, c, kvh, hd), -127, 128, jnp.int8),
            "v": jax.random.randint(ks[2], (b, c, kvh, hd), -127, 128, jnp.int8),
            "k_scale": (jax.random.uniform(ks[3], (b, c, kvh)) * 0.05 + 0.01
                        ).astype(jnp.bfloat16),
            "v_scale": (jax.random.uniform(ks[4], (b, c, kvh)) * 0.05 + 0.01
                        ).astype(jnp.bfloat16),
        }
    else:
        cache = {
            "k": jax.random.normal(ks[1], (b, c, kvh, hd), dtype),
            "v": jax.random.normal(ks[2], (b, c, kvh, hd), dtype),
        }
    return q, cache


def _oracle(q, cache, n_valid, softcap=0.0):
    """Full-cache dequant + validity-masked naive softmax (the old path)."""
    k, v = _read_cache(cache, q.dtype)
    c = k.shape[1]
    mask = (jnp.arange(c)[None, :] < n_valid)[:, None, None, None, :]
    return _naive_attn(q, k, v, mask, softcap)


class TestKernelRefEquivalence:
    """Kernel (interpret) vs the jnp fallback: same arithmetic recipe, two
    lowered programs — agreement is float-ulp level (XLA fusion/FMA
    reassociation is the only difference), far below the ~1e-2 the bf16
    model dtype resolves."""

    @pytest.mark.parametrize("g", [1, 4])
    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("n_valid", [1, BKV - 1, BKV, 32])
    def test_kernel_interpret_equals_ref(self, g, quantized, n_valid):
        b, c, kvh, hd = 2, 32, 2, 16
        q, cache = _make_qcache(0, b, c, kvh, g, hd, quantized)
        n = jnp.full((b, 1), n_valid, jnp.int32)
        args = (q[:, 0], cache["k"], cache["v"],
                cache.get("k_scale"), cache.get("v_scale"), n)
        out_k = flash_decode_kernel(*args, block_kv=BKV, interpret=True)
        out_r = flash_decode_ref(*args, block_kv=BKV)
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=2e-6, atol=2e-6,
        )

    @pytest.mark.parametrize("softcap", [0.0, 30.0])
    def test_softcap_paths_agree(self, softcap):
        b, c, kvh, g, hd = 1, 16, 2, 2, 8
        q, cache = _make_qcache(1, b, c, kvh, g, hd, False)
        n = jnp.full((b, 1), 11, jnp.int32)
        args = (q[:, 0], cache["k"], cache["v"], None, None, n)
        out_k = flash_decode_kernel(*args, block_kv=BKV, softcap=softcap,
                                    interpret=True)
        out_r = flash_decode_ref(*args, block_kv=BKV, softcap=softcap)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_r), rtol=2e-6, atol=2e-6
        )

    def test_bf16_query_int8_cache(self):
        """Production serve dtype: bf16 activations over the int8 cache —
        outputs round to bf16, so the two paths agree to a bf16 ulp."""
        b, c, kvh, g, hd = 2, 32, 2, 4, 16
        q, cache = _make_qcache(4, b, c, kvh, g, hd, True,
                                dtype=jnp.bfloat16)
        n = jnp.full((b, 1), 13, jnp.int32)
        args = (q[:, 0], cache["k"], cache["v"],
                cache["k_scale"], cache["v_scale"], n)
        out_k = flash_decode_kernel(*args, block_kv=BKV, interpret=True)
        out_r = flash_decode_ref(*args, block_kv=BKV)
        assert out_k.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=1e-2,
        )


class TestRefVsNaiveOracle:
    @pytest.mark.parametrize("g", [1, 4])
    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("n_valid", [1, BKV - 1, BKV, 32])
    def test_matches_masked_naive(self, g, quantized, n_valid):
        b, c, kvh, hd = 2, 32, 2, 16
        q, cache = _make_qcache(2, b, c, kvh, g, hd, quantized)
        out = decode_attention(
            q, cache, jnp.int32(n_valid), block_kv=BKV, impl="ref"
        )
        want = _oracle(q, cache, jnp.int32(n_valid))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=1e-5,
        )

    def test_per_request_n_valid_vector(self):
        """Per-request lengths (the DecodeEngine batch case)."""
        b, c, kvh, g, hd = 3, 32, 2, 2, 16
        q, cache = _make_qcache(3, b, c, kvh, g, hd, True)
        n = jnp.array([1, 9, 32], jnp.int32)
        out = decode_attention(q, cache, n, block_kv=BKV, impl="ref")
        for i in range(b):
            want = _oracle(q[i : i + 1],
                           {k: v[i : i + 1] for k, v in cache.items()},
                           n[i])
            np.testing.assert_allclose(
                np.asarray(out[i : i + 1], np.float32),
                np.asarray(want, np.float32), atol=1e-5,
            )


class TestSlotVmap:
    @pytest.mark.parametrize("impl", ["ref", "kernel"])
    def test_vmap_over_slots_with_per_slot_index(self, impl):
        """The slot-pool contract: vmap over a leading slot axis with a
        per-slot cache_index equals the per-slot loop."""
        slots, c, kvh, g, hd = 3, 16, 2, 2, 8
        qs, caches = [], []
        for i in range(slots):
            q, cache = _make_qcache(10 + i, 1, c, kvh, g, hd, True)
            qs.append(q)
            caches.append(cache)
        q_sl = jnp.concatenate(qs)[:, None][:, 0]            # (S, 1, KV, G, hd)
        cache_sl = {k: jnp.concatenate([cc[k] for cc in caches])
                    for k in caches[0]}
        n_sl = jnp.array([1, 7, 16], jnp.int32)

        fn = lambda q, cache, n: decode_attention(
            q[None], {k: v[None] for k, v in cache.items()}, n,
            block_kv=BKV, impl=impl, interpret=True,
        )[0]
        out = jax.vmap(fn)(q_sl, cache_sl, n_sl)
        for i in range(slots):
            want = decode_attention(
                qs[i], caches[i], n_sl[i], block_kv=BKV, impl="ref"
            )
            np.testing.assert_allclose(
                np.asarray(out[i], np.float32),
                np.asarray(want[0], np.float32), atol=1e-6,
            )


def _stacked_pool(seed, slots, units, c, kvh, hd, quantized):
    """A slot pool's stacked cache of one layer, ``(S, U, 1, C, KV*hd)``
    rows (plus ``(S, U, 1, C, KV)`` scales when int8), as the engine
    carries it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    lead = (slots, units, 1, c)
    if quantized:
        return {
            "k": jax.random.randint(ks[0], lead + (kvh * hd,), -127, 128,
                                    jnp.int8),
            "v": jax.random.randint(ks[1], lead + (kvh * hd,), -127, 128,
                                    jnp.int8),
            "k_scale": (jax.random.uniform(ks[2], lead + (kvh,)) * 0.05
                        + 0.01).astype(jnp.bfloat16),
            "v_scale": (jax.random.uniform(ks[3], lead + (kvh,)) * 0.05
                        + 0.01).astype(jnp.bfloat16),
        }
    return {
        "k": jax.random.normal(ks[0], lead + (kvh * hd,), jnp.bfloat16),
        "v": jax.random.normal(ks[1], lead + (kvh * hd,), jnp.bfloat16),
    }


def _unit_layer(pool, unit):
    """Slot ``s``'s layer cache at ``unit``, ``(S, C, KV*hd)``."""
    return {name: a[:, unit, 0] for name, a in pool.items()}


def _slot_step(impl, block_kv=BKV):
    """The engine's form: a batch-1 call on one slot's stacked cache,
    vmapped over the slots with a per-slot ``n_valid``."""
    def one(q, cache, n, unit):
        return decode_attention(
            q[None], cache, n, unit=unit, block_kv=block_kv, impl=impl,
            interpret=True,
        )[0]

    return jax.vmap(one, in_axes=(0, 0, 0, None))


class TestStackedPool:
    """The kernel reads unit ``unit`` of the stacked slot pool in place and
    walks only each slot's valid blocks; one ``pallas_call`` under vmap."""

    SLOTS, UNITS, C, KVH, G, HD = 2, 3, 32, 2, 2, 16

    @pytest.mark.parametrize("unit", [0, 1, 2])
    @pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("n_valid", [1, BKV, BKV + 1, 32])
    def test_every_unit_equals_ref(self, unit, quantized, n_valid):
        s, u, c, kvh, g, hd = (self.SLOTS, self.UNITS, self.C, self.KVH,
                               self.G, self.HD)
        pool = _stacked_pool(40 + unit, s, u, c, kvh, hd, quantized)
        q = jax.random.normal(jax.random.PRNGKey(unit), (s, 1, kvh, g, hd),
                              jnp.bfloat16)
        n = jnp.full((s,), n_valid, jnp.int32)
        got = _slot_step("kernel")(q, pool, n, jnp.int32(unit))
        layer = _unit_layer(pool, unit)
        want = decode_attention(q, layer, n, block_kv=BKV, impl="ref")
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2, atol=1e-2,
        )
        oracle = _oracle(q.astype(jnp.float32),
                         {k: a.reshape(a.shape[:2] + (kvh, -1))
                          if k in ("k", "v") else a
                          for k, a in layer.items()}, n[:, None])
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(oracle, np.float32),
            rtol=2e-2, atol=2e-2,
        )

    @pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
    def test_vmap_per_slot_n_valid(self, quantized):
        """Slots of different lengths in one call, each against its own
        batch-1 reference."""
        s, u, c, kvh, g, hd = 4, 2, self.C, self.KVH, self.G, self.HD
        pool = _stacked_pool(50, s, u, c, kvh, hd, quantized)
        q = jax.random.normal(jax.random.PRNGKey(5), (s, 1, kvh, g, hd))
        n = jnp.array([1, BKV, BKV + 1, c], jnp.int32)
        got = _slot_step("kernel")(q, pool, n, jnp.int32(1))
        layer = _unit_layer(pool, 1)
        for i in range(s):
            want = decode_attention(
                q[i : i + 1], {k: a[i : i + 1] for k, a in layer.items()},
                n[i], block_kv=BKV, impl="ref",
            )
            np.testing.assert_allclose(
                np.asarray(got[i], np.float32),
                np.asarray(want[0], np.float32), rtol=2e-6, atol=2e-6,
                err_msg=f"slot {i}, n_valid {int(n[i])}",
            )

    @pytest.mark.parametrize("impl", ["kernel", "ref"])
    def test_windowed_cache_across_wrap(self, impl):
        """A rotating window of C rows written past its wrap: the live rows
        stay the prefix ``[0, min(index + 1, C))`` and each step attends
        over exactly the last C positions written."""
        u, c, kvh, g, hd = 2, 16, self.KVH, self.G, self.HD
        rng = jax.random.split(jax.random.PRNGKey(60), 3)
        rows_k = jax.random.normal(rng[0], (24, kvh * hd))
        rows_v = jax.random.normal(rng[1], (24, kvh * hd))
        q = jax.random.normal(rng[2], (1, 1, kvh, g, hd))
        pool = {"k": jnp.zeros((1, u, 1, c, kvh * hd)),
                "v": jnp.zeros((1, u, 1, c, kvh * hd))}
        for index in range(24):
            for name, rows in (("k", rows_k), ("v", rows_v)):
                pool[name] = pool[name].at[0, 1, 0, index % c].set(rows[index])
            n = jnp.minimum(jnp.int32(index) + 1, c)[None]
            got = _slot_step(impl, block_kv=4)(q, pool, n, jnp.int32(1))
            live = jnp.arange(max(0, index + 1 - c), index + 1)
            heads = lambda a: a[live].reshape(1, -1, kvh, hd)
            want = _oracle(q, {"k": heads(rows_k), "v": heads(rows_v)},
                           jnp.int32(live.shape[0]))
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5,
                err_msg=f"index {index}",
            )

    def test_vmapped_call_is_one_pallas_call(self):
        """vmapped over the slots, the walk binds one ``pallas_call`` with
        the slots as its request axis, and no loop runs around it (the
        per-slot fallback ``pallas_call`` takes for batched scalar
        prefetch is a loop of calls)."""
        s, u, c, kvh, g, hd = 4, 3, self.C, self.KVH, self.G, self.HD
        pool = _stacked_pool(70, s, u, c, kvh, hd, True)
        q = jnp.zeros((s, 1, kvh, g, hd), jnp.bfloat16)
        n = jnp.arange(1, s + 1, dtype=jnp.int32)
        jaxpr = jax.make_jaxpr(_slot_step("kernel"))(q, pool, n, jnp.int32(2))

        def prims(jx):
            for eqn in jx.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from prims(sub)

        eqns = list(prims(jaxpr.jaxpr))
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        assert not [e.primitive.name for e in eqns
                    if e.primitive.name in ("while", "scan")]
        # the request axis is every slot; K/V are the whole pool
        assert calls[0].outvars[0].aval.shape[0] == s
        pool_rows = {tuple(v.aval.shape) for v in calls[0].invars}
        assert (s * u, c, kvh * hd) in pool_rows


class TestRotatingWindowIntegration:
    def test_windowed_decode_across_wrap(self):
        """Sliding-window layer stepped past the wrap point: flash_decode
        logits match the naive oracle at every step (window=8, 14 steps)."""
        from repro.models import cache as cache_lib

        cfg_n = ARCHITECTURES["gemma3-12b"].reduced()
        pat = tuple(dataclasses.replace(s, window=8) if s.window else s
                    for s in cfg_n.unit_pattern)
        cfg_n = cfg_n.with_updates(unit_pattern=pat)
        cfg_f = cfg_n.with_updates(attn_impl="flash_decode")
        params = lm.init_lm(jax.random.PRNGKey(0), cfg_n)
        caches = {
            "naive": cache_lib.init_cache(cfg_n, 1, 16),
            "flash": cache_lib.init_cache(cfg_f, 1, 16),
        }
        tok = jnp.array([[3]], jnp.int32)
        for i in range(14):
            ln, caches["naive"], _ = lm.forward(
                params, tok, cfg_n, cache=caches["naive"],
                cache_index=jnp.int32(i), mode="decode",
            )
            lf, caches["flash"], _ = lm.forward(
                params, tok, cfg_f, cache=caches["flash"],
                cache_index=jnp.int32(i), mode="decode",
            )
            np.testing.assert_allclose(
                np.asarray(lf), np.asarray(ln), atol=2e-4,
                err_msg=f"step {i}",
            )
            tok = jnp.argmax(ln, -1).astype(jnp.int32)


class TestNaiveFallbackPrefixSlice:
    @pytest.mark.parametrize("quantized", [False, True])
    def test_prefix_slice_equals_masked_form(self, quantized):
        """Satellite fix: a concrete ``cache_index`` dequantizes/reads only
        the valid prefix.  Must match the traced (jitted) masked form —
        identical math, so agreement is pinned at ulp level."""
        from repro.models.attention import _masked_decode_attn

        b, c, kvh, g, hd = 2, 24, 2, 2, 16
        for idx in (0, 5, 23, 30):               # 30 > C: wrapped window
            q, cache = _make_qcache(20 + idx, b, c, kvh, g, hd, quantized)
            sliced = _masked_decode_attn(q, cache, idx, 0.0, q.dtype)
            masked = jax.jit(  # noqa: RPA001 — compile per idx is the point: the tracer must hit the masked branch
                lambda i, q=q, cache=cache: _masked_decode_attn(
                    q, cache, i, 0.0, q.dtype
                )
            )(jnp.int32(idx))                     # tracer -> masked branch
            np.testing.assert_allclose(
                np.asarray(sliced, np.float32), np.asarray(masked, np.float32),
                rtol=2e-6, atol=2e-6,
            )

    @pytest.mark.parametrize("kv_cache_dtype", ["", "int8"])
    def test_lm_decode_concrete_vs_traced_index(self, kv_cache_dtype):
        """End-to-end: un-jitted decode steps (concrete index -> prefix
        slice) track the jitted masked steps through the full stack."""
        from repro.models import cache as cache_lib

        cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced(
            kv_cache_dtype=kv_cache_dtype
        )
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        step = jax.jit(
            lambda p, t, c, i: lm.forward(p, t, cfg, cache=c, cache_index=i,
                                          mode="decode"),
        )
        cache_c = cache_lib.init_cache(cfg, 1, 12)
        cache_t = cache_lib.init_cache(cfg, 1, 12)
        tok = jnp.array([[7]], jnp.int32)
        for i in range(5):
            lc, cache_c, _ = lm.forward(
                params, tok, cfg, cache=cache_c, cache_index=i, mode="decode"
            )  # Python int index -> prefix-slice path
            lt, cache_t, _ = step(params, tok, cache_t, jnp.int32(i))
            np.testing.assert_allclose(
                np.asarray(lc), np.asarray(lt), rtol=1e-4, atol=1e-4,
                err_msg=f"step {i}",
            )
            tok = jnp.argmax(lt, -1).astype(jnp.int32)


def _setup_engine(channel="iid", loss_rate=0.3, **overrides):
    cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced(
        attn_impl="flash_decode", **overrides
    )
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=loss_rate, channel=channel)
    )
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(i, length, vocab):
    return np.asarray(
        jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(7), i), (length,), 0, vocab,
            jnp.int32,
        )
    )


class TestContinuousEngineFlashDecode:
    @pytest.mark.parametrize("channel", ["iid", "ge"])
    def test_token_identity_vs_reference(self, channel):
        """Acceptance: attn_impl="flash_decode" greedy outputs are
        token-identical to the reference loop, mixed buckets, iid + GE."""
        cfg, params = _setup_engine(channel=channel)
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=2, max_new=4, max_prompt=16, min_bucket=4)
        )
        key = jax.random.PRNGKey(42)
        lengths = [1, 3, 6, 13]
        reqs = [
            eng.submit(_prompt(i, L, cfg.vocab_size), 4,
                       key=jax.random.fold_in(key, i))
            for i, L in enumerate(lengths)
        ]
        eng.run(params)
        for i, (L, req) in enumerate(zip(lengths, reqs)):
            ref, _ = generate_reference(
                params, cfg, jnp.asarray(_prompt(i, L, cfg.vocab_size))[None],
                4, key=jax.random.fold_in(key, i),
            )
            np.testing.assert_array_equal(
                np.asarray(ref)[0], req.tokens,
                err_msg=f"request {i} (len {L}, channel {channel})",
            )

    def test_int8_cache_token_identity(self):
        """flash_decode + int8 slot-pool cache (the config the perf win
        targets) still matches the reference loop exactly."""
        cfg, params = _setup_engine(kv_cache_dtype="int8")
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=2, max_new=5, max_prompt=8, min_bucket=8)
        )
        key = jax.random.PRNGKey(9)
        reqs = [
            eng.submit(_prompt(i, 4 + i, cfg.vocab_size), 5,
                       key=jax.random.fold_in(key, i))
            for i in range(3)
        ]
        eng.run(params)
        for i, req in enumerate(reqs):
            ref, _ = generate_reference(
                params, cfg, jnp.asarray(_prompt(i, 4 + i, cfg.vocab_size))[None],
                5, key=jax.random.fold_in(key, i),
            )
            np.testing.assert_array_equal(np.asarray(ref)[0], req.tokens)

    def test_compiles_still_buckets_plus_one(self):
        """Zero-steady-state regression with the masked decode step."""
        cfg, params = _setup_engine()
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=3, max_new=4, max_prompt=16, min_bucket=8)
        )
        key = jax.random.PRNGKey(0)
        for i, L in enumerate([5, 12, 7, 16]):    # buckets {8, 16}
            eng.submit(_prompt(i, L, cfg.vocab_size), 3,
                       key=jax.random.fold_in(key, i))
        eng.run(params)
        assert eng.num_buckets == 2
        assert eng.compiles == eng.num_buckets + 1
        warm = eng.compiles
        for i in range(8):
            eng.submit(_prompt(100 + i, 4 + (i % 13), cfg.vocab_size),
                       1 + (i % 4), key=jax.random.fold_in(key, 100 + i))
        done = eng.run(params)
        assert len(done) == 8
        assert eng.compiles == warm

    def test_attn_impl_override_arg(self):
        """Engine-level attn_impl override rebuilds the config."""
        cfg, params = _setup_engine()
        base = cfg.with_updates(attn_impl="naive")
        eng = ContinuousEngine(
            base, PoolConfig(max_slots=1, max_new=2, max_prompt=8),
            attn_impl="flash_decode",
        )
        assert eng.cfg.attn_impl == "flash_decode"
        req = eng.submit(_prompt(0, 4, cfg.vocab_size), 2)
        eng.run(params)
        assert req.tokens is not None and req.tokens.shape == (2,)


class TestHelpers:
    def test_decode_block_kv_divides_or_pads(self):
        """The block comes from the cache's shape: about 128 KB of K rows,
        a divisor of the length in whole packed tiles, else the aim (the
        op pads)."""
        assert decode_block_kv(320, 1024, 2) == 64    # qwen1.5-0.5b, bf16
        assert decode_block_kv(768, 512, 2) == 128    # codeqwen1.5-7b, bf16
        assert decode_block_kv(320, 1024, 1) == 64    # int8: aim 128
        assert decode_block_kv(7, 1024, 2) == 7       # single block, no pad
        assert decode_block_kv(1024, 32, 4) == 1024   # tiny rows: one block
        # Coprime-ish lengths keep a real block (ops pads the cache)
        # instead of collapsing to micro-blocks.
        assert decode_block_kv(1000, 1024, 2) == 64
        assert decode_block_kv(4100, 512, 2) == 128
        for c, lanes, size in [(320, 1024, 2), (768, 512, 2), (192, 1024, 1),
                               (4096, 256, 2), (48, 4096, 4), (24, 32, 4)]:
            bkv = decode_block_kv(c, lanes, size)
            assert c % bkv == 0
            assert bkv == c or bkv % (32 // size) == 0

    @pytest.mark.parametrize("impl", ["ref", "kernel"])
    def test_degenerate_cache_length_pads_correctly(self, impl):
        """C=65 has no usable divisor of 64: the pad path must still match
        the full-cache oracle for valid lengths inside AND at C."""
        b, c, kvh, g, hd = 2, 65, 2, 2, 16
        q, cache = _make_qcache(30, b, c, kvh, g, hd, True)
        for n_valid in (3, 64, 65):
            out = decode_attention(
                q, cache, jnp.int32(n_valid), block_kv=64, impl=impl,
                interpret=True,
            )
            want = _oracle(q, cache, jnp.int32(n_valid))
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(want, np.float32),
                atol=1e-5, err_msg=f"n_valid={n_valid} impl={impl}",
            )

    def test_invalid_impl_raises(self):
        b, c, kvh, g, hd = 1, 16, 1, 1, 8
        q, cache = _make_qcache(31, b, c, kvh, g, hd, False)
        with pytest.raises(ValueError, match="unknown decode-attention"):
            decode_attention(q, cache, jnp.int32(4), impl="naive")

    def test_decode_read_bytes_scales_with_valid(self):
        from repro.models.cache import decode_read_bytes

        cfg = ARCHITECTURES["qwen1.5-0.5b"].with_updates(kv_cache_dtype="int8")
        full = decode_read_bytes(cfg, 1024, 1024, masked=False)
        assert decode_read_bytes(cfg, 1024, 1024, masked=True) == full
        small = decode_read_bytes(cfg, 1024, 16, masked=True)
        assert small * 8 <= full                  # 1/16 of the cache ±block
        assert decode_read_bytes(cfg, 1024, 16, masked=False) == full
