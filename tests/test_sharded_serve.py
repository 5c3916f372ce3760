"""Sharded serving: the host-mesh builders, the occupancy-aware router
(``repro.serve.router.ShardedEngine``), cross-shard preempt/resume token
identity, aggregated backpressure, steady-state compile discipline, and
the forced-4-device end-to-end path.

Most router logic is exercised IN-PROCESS by pinning several shards to
the single CPU device (``devices=[dev, dev]`` — placement, global slot
numbering, preemption forwarding, and the per-shard compile accounting
are all host-side and device-count-independent).  The real multi-device
behavior needs ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
set before the backend initializes, so it runs in a subprocess.
"""

import dataclasses
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.guards import no_recompile
from repro.configs import ARCHITECTURES
from repro.launch.mesh import HOST_DEVICES_ENV, host_devices, make_host_mesh
from repro.launch.serve import generate_reference
from repro.models import lm
from repro.net import ChaosSchedule, block_pool_squeeze
from repro.net.chaos import EngineChaos
from repro.serve import (
    PoolConfig,
    PoolExhausted,
    ShardedEngine,
    SLA,
    SLAScheduler,
)

REPO_ROOT = Path(__file__).resolve().parent.parent


def _setup(channel="iid", loss_rate=0.3, **overrides):
    cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced(
        attn_impl="flash_decode", **overrides
    )
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, loss_rate=loss_rate,
                                 channel=channel)
    )
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(i, length, vocab):
    return np.asarray(
        jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(7), i), (length,), 0,
            vocab, jnp.int32,
        )
    )


def _two_shard(cfg, pool):
    dev = jax.devices()[0]
    return ShardedEngine(cfg, pool, devices=[dev, dev])


def _check_reference(cfg, params, reqs, base_key):
    for i, req in enumerate(reqs):
        ref, _ = generate_reference(
            params, cfg, req.prompt[None], req.max_tokens,
            key=jax.random.fold_in(base_key, i),
        )
        np.testing.assert_array_equal(req.tokens, np.asarray(ref)[0])


# ---------------------------------------------------------------------------
# launch.mesh: deterministic host meshes + overrides (satellite)
# ---------------------------------------------------------------------------


class TestHostMesh:
    def test_explicit_devices_win(self):
        devs = jax.devices()
        mesh = make_host_mesh(devices=devs[:1])
        assert mesh.axis_names == ("data", "model")
        assert mesh.shape == {"data": 1, "model": 1}

    def test_empty_devices_rejected(self):
        with pytest.raises(ValueError, match="empty"):
            host_devices([])

    def test_model_axis_must_divide(self):
        with pytest.raises(ValueError, match="does not divide"):
            make_host_mesh(3, devices=jax.devices()[:1])

    def test_model_axis_positive(self):
        with pytest.raises(ValueError, match=">= 1"):
            make_host_mesh(0, devices=jax.devices()[:1])

    def test_env_override_too_many_fails_loudly(self, monkeypatch):
        monkeypatch.setenv(HOST_DEVICES_ENV, str(len(jax.devices()) + 1))
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            host_devices()

    def test_env_override_negative_rejected(self, monkeypatch):
        monkeypatch.setenv(HOST_DEVICES_ENV, "-2")
        with pytest.raises(ValueError, match=">= 0"):
            host_devices()

    def test_env_override_selects_prefix(self, monkeypatch):
        monkeypatch.setenv(HOST_DEVICES_ENV, "1")
        assert host_devices() == jax.devices()[:1]


# ---------------------------------------------------------------------------
# Router: placement-invariant token identity + per-shard compile contract
# ---------------------------------------------------------------------------


class TestRouterTokenIdentity:
    @pytest.mark.parametrize("channel", ["iid", "ge"])
    def test_matches_reference_across_shards(self, channel):
        cfg, params = _setup(channel=channel)
        eng = _two_shard(cfg, PoolConfig(max_slots=2, max_new=8,
                                         max_prompt=16))
        base = jax.random.PRNGKey(42)
        lengths = (5, 9, 12, 7, 16)
        reqs = [
            eng.submit(_prompt(i, n, cfg.vocab_size), 6,
                       key=jax.random.fold_in(base, i))
            for i, n in enumerate(lengths)
        ]
        done = eng.run(params)
        assert len(done) == len(lengths)
        # Both shards must actually have served traffic, or the test says
        # nothing about placement invariance.
        assert all(c > 0 for c in eng.placement_counts), \
            eng.placement_counts
        _check_reference(cfg, params, reqs, base)

    def test_int8_kv_cache(self):
        cfg, params = _setup(kv_cache_dtype="int8")
        eng = _two_shard(cfg, PoolConfig(max_slots=2, max_new=8,
                                         max_prompt=16))
        base = jax.random.PRNGKey(3)
        reqs = [
            eng.submit(_prompt(i, n, cfg.vocab_size), 5,
                       key=jax.random.fold_in(base, i))
            for i, n in enumerate((6, 11, 14))
        ]
        eng.run(params)
        _check_reference(cfg, params, reqs, base)

    def test_per_shard_compiles_is_buckets_plus_one(self):
        cfg, params = _setup()
        eng = _two_shard(cfg, PoolConfig(max_slots=2, max_new=8,
                                         max_prompt=16))
        base = jax.random.PRNGKey(1)
        for i, n in enumerate((5, 9, 12, 7)):     # buckets 8 and 16
            eng.submit(_prompt(i, n, cfg.vocab_size), 4,
                       key=jax.random.fold_in(base, i))
        eng.run(params)
        for sh in eng.shards:
            assert sh.num_buckets == 2
            assert sh.compiles == sh.num_buckets + 1, (
                sh.compiles, sh.num_buckets
            )
        assert eng.compiles == sum(sh.compiles for sh in eng.shards)

    def test_more_shards_than_chips_raises_off_cpu(self, monkeypatch):
        """On an accelerator, asking for more shards than chips is an
        error: wrapping would stack pools on one chip."""
        from repro.serve import router

        cfg, _ = _setup()
        chip = types.SimpleNamespace(platform="tpu")
        monkeypatch.setattr(router.jax, "devices", lambda: [chip])
        with pytest.raises(ValueError, match="1 tpu devices"):
            router.sharded_engine(cfg, PoolConfig(), num_shards=2)

    def test_placement_prefers_freest_shard(self):
        """With shard0 loaded and shard1 idle, the next admission must go
        to shard1; ties break toward the lower index."""
        cfg, params = _setup()
        eng = _two_shard(cfg, PoolConfig(max_slots=2, max_new=8,
                                         max_prompt=16))
        base = jax.random.PRNGKey(9)
        r0 = eng.submit(_prompt(0, 8, cfg.vocab_size), 8, key=base)
        eng.step(params)                     # admit r0 (tie -> shard 0)
        assert eng.placements[r0.rid] == [0]
        r1 = eng.submit(_prompt(1, 8, cfg.vocab_size), 8,
                        key=jax.random.fold_in(base, 1))
        eng.step(params)                     # shard1 now strictly freer
        assert eng.placements[r1.rid] == [1]
        eng.run(params)


# ---------------------------------------------------------------------------
# Cross-shard preempt/resume (scheduler-driven) token identity
# ---------------------------------------------------------------------------


class TestCrossShardPreemptResume:
    @pytest.mark.parametrize(
        "channel,overrides",
        [("iid", {}), ("ge", {}), ("iid", {"kv_cache_dtype": "int8"})],
        ids=["iid", "ge", "int8"],
    )
    def test_preempt_on_a_resume_on_b(self, channel, overrides):
        """Preempt a request off shard 0 and let it resume on shard 1:
        the keyed math is placement-invariant, so tokens must equal an
        uninterrupted single-device reference run."""
        cfg, params = _setup(channel=channel, **overrides)
        eng = _two_shard(cfg, PoolConfig(max_slots=1, max_new=32,
                                         max_prompt=16))
        sched = SLAScheduler(backoff_s=0.0, max_retries=10_000)
        eng.attach_scheduler(sched)
        base = jax.random.PRNGKey(11)
        # A: best-effort (inf deadline -> preferred preemption victim).
        ra = eng.submit(_prompt(0, 7, cfg.vocab_size), 8,
                        key=jax.random.fold_in(base, 0))
        eng.step(params)
        assert eng.placements[ra.rid] == [0]
        # B: same priority, finite deadline -> kept; fills shard 1, and
        # retires first so shard 1 is where A's resume lands.
        rb = eng.submit(_prompt(1, 5, cfg.vocab_size), 4,
                        key=jax.random.fold_in(base, 1),
                        sla=SLA(deadline_s=60.0))
        eng.step(params)
        assert eng.placements[rb.rid] == [1]
        # C: higher priority, long-running -> preempts A off shard 0 and
        # keeps shard 0 busy until well after A resumes.
        rc = eng.submit(_prompt(2, 9, cfg.vocab_size), 24,
                        key=jax.random.fold_in(base, 2),
                        sla=SLA(priority=5))
        done = eng.run(params)
        assert len(done) == 3
        assert ra.n_preempts == 1
        assert eng.placements[ra.rid] == [0, 1], eng.placements
        assert eng.placements[rc.rid] == [0]
        assert sched.stats["preemptions"] == 1
        assert sched.stats["resumes"] == 1
        _check_reference(cfg, params, [ra, rb, rc], base)


# ---------------------------------------------------------------------------
# Backpressure: all shards exhausted -> aggregated PoolExhausted
# ---------------------------------------------------------------------------


class TestAllShardsExhausted:
    def test_typed_fields_aggregate_across_shards(self):
        cfg, params = _setup()
        pool = PoolConfig(max_slots=2, max_new=8, max_prompt=16,
                          paged=True, block_size=4, exhaust_wait_steps=3)
        eng = _two_shard(cfg, pool)
        # A chaos squeeze holds EVERY allocatable block on EVERY shard.
        chaos = EngineChaos(
            eng, ChaosSchedule([block_pool_squeeze(0.0, 100.0, 1.0)])
        )
        chaos.apply(now=1.0)
        per_shard = pool.total_blocks - 1
        assert chaos.held_blocks == 2 * per_shard
        req = eng.submit(_prompt(0, 8, cfg.vocab_size), 4,
                         key=jax.random.PRNGKey(5))
        with pytest.raises(PoolExhausted) as exc:
            for _ in range(pool.exhaust_wait_steps + 2):
                eng.step(params)
        e = exc.value
        assert e.queued == 1
        assert e.free_slots == 4          # sum across shards: 2 x 2 slots
        assert e.free_blocks == 0         # sum across shards, all held
        assert e.need_blocks == eng.blocks_needed(8, 4) > 0
        # Release the squeeze: the same queue drains normally.
        chaos.release_all()
        assert chaos.held_blocks == 0
        done = eng.run(params)
        assert len(done) == 1 and done[0] is req
        ref, _ = generate_reference(
            params, cfg, req.prompt[None], 4, key=jax.random.PRNGKey(5)
        )
        np.testing.assert_array_equal(req.tokens, np.asarray(ref)[0])


# ---------------------------------------------------------------------------
# Steady state: zero builds over a mixed-shard workload after warm()
# ---------------------------------------------------------------------------


class TestRouterNoRecompile:
    def test_steady_state_mixed_shard_workload(self):
        cfg, params = _setup()
        eng = _two_shard(cfg, PoolConfig(max_slots=2, max_new=8,
                                         max_prompt=16))
        lengths = (5, 9, 12, 7, 16, 6)
        eng.warm(params, lengths)
        for sh in eng.shards:
            assert sh.compiles == sh.num_buckets + 1
        # Precompute prompts/keys: fold_in itself compiles a tiny program
        # on first use, which is warm-up work, not serving work.
        base = jax.random.PRNGKey(13)
        prompts = [_prompt(i, n, cfg.vocab_size) for i, n in
                   enumerate(lengths)]
        keys = [jax.random.fold_in(base, i) for i in range(len(lengths))]
        jax.block_until_ready(keys)
        with no_recompile(engines=(eng, *eng.shards)):
            reqs = [
                eng.submit(p, 6, key=k) for p, k in zip(prompts, keys)
            ]
            done = eng.run(params)
        assert len(done) == len(lengths)
        assert all(c > 0 for c in eng.placement_counts)
        for sh in eng.shards:
            assert sh.compiles == sh.num_buckets + 1
        _check_reference(cfg, params, reqs, base)


# ---------------------------------------------------------------------------
# The real thing: forced 4-device host mesh (subprocess)
# ---------------------------------------------------------------------------


class TestForcedMultiDevice:
    def test_router_on_four_devices(self):
        code = """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
import pytest
from repro.configs import ARCHITECTURES
from repro.launch.mesh import make_host_mesh, host_devices
from repro.launch.serve import generate_reference
from repro.models import lm
from repro.serve import PoolConfig, ShardedEngine
from repro.sharding.rules import pool_shard_devices

assert len(jax.devices()) == 4, jax.devices()

# Mesh builders under the forced backend.
mesh = make_host_mesh()
assert mesh.shape == {"data": 4, "model": 1}
devs = pool_shard_devices(mesh)
assert len(devs) == 4 and len({d.id for d in devs}) == 4
try:
    pool_shard_devices(make_host_mesh(4))
except ValueError as e:
    assert "slot" in str(e)
else:
    raise AssertionError("model-axis>1 mesh must be rejected")
import os
os.environ["REPRO_HOST_DEVICES"] = "2"
assert len(host_devices()) == 2
del os.environ["REPRO_HOST_DEVICES"]

cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced(attn_impl="flash_decode")
cfg = cfg.with_updates(
    link=dataclasses.replace(cfg.link, loss_rate=0.3, channel="ge")
)
params = lm.init_lm(jax.random.PRNGKey(0), cfg)
eng = ShardedEngine(
    cfg, PoolConfig(max_slots=1, max_new=8, max_prompt=16), mesh=mesh
)
assert eng.num_shards == 4
base = jax.random.PRNGKey(21)
lengths = (5, 9, 12, 7, 16, 6, 11, 8)
reqs = [
    eng.submit(
        np.asarray(jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(7), i), (n,), 0,
            cfg.vocab_size, jnp.int32,
        )), 6, key=jax.random.fold_in(base, i))
    for i, n in enumerate(lengths)
]
done = eng.run(params)
assert len(done) == len(lengths)
assert all(c > 0 for c in eng.placement_counts), eng.placement_counts
for sh in eng.shards:
    assert sh.compiles == sh.num_buckets + 1, (sh.compiles, sh.num_buckets)
    assert sh.devices_in_use() == {sh.device}, (sh.devices_in_use(), sh.device)
assert len({sh.device for sh in eng.shards}) == 4
for i, req in enumerate(reqs):
    ref, _ = generate_reference(
        params, cfg, req.prompt[None], req.max_tokens,
        key=jax.random.fold_in(base, i),
    )
    np.testing.assert_array_equal(req.tokens, np.asarray(ref)[0])
print("OK_4DEV_ROUTER")
"""
        env = dict(os.environ)
        env.pop(HOST_DEVICES_ENV, None)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["JAX_PLATFORMS"] = "cpu"
        src = str(REPO_ROOT / "src")
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        r = subprocess.run(
            [sys.executable, "-c", code], env=env,
            capture_output=True, text=True, timeout=540,
        )
        assert r.returncode == 0 and "OK_4DEV_ROUTER" in r.stdout, (
            r.stdout[-2000:], r.stderr[-4000:]
        )
