"""Observability (repro.obs): registry, streaming histograms, exporters,
trace-time link taps, and the engine's on-device counters.

The two load-bearing guarantees:

* **Obs never changes the programs.**  The slot-pool engine carries its
  ``DeviceCounters`` pytree unconditionally, so enabling the registry adds
  ZERO XLA compiles, keeps ``compiles == num_buckets + 1``, and greedy
  outputs stay token-identical to ``generate_reference`` (iid + GE).
* **The device counters are exact.**  The realized link statistics
  harvested from the engine equal an eager oracle that replays the
  per-request key chain through ``lm.make_link_fn`` (the identical
  ``emulate_link`` closure) on zero messages of the engine's shapes —
  mask draws depend only on (key, shape), so the oracle reproduces every
  engine draw including the padded bucket positions.
"""

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import ARCHITECTURES
from repro.launch.serve import generate_reference
from repro.analysis.guards import no_recompile
from repro.models import cache as cache_lib, lm
from repro.obs import device as obs_device, exporters
from repro.obs.registry import Registry
from repro.obs.stats import StreamingHistogram, latency_summary, percentile
from repro.serve import ContinuousEngine, PoolConfig


def _setup(channel="iid", loss_rate=0.3):
    cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced()
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


@pytest.fixture
def global_registry_enabled():
    """Enable the process-global registry for one test, restore after."""
    reg = obs.registry()
    was = reg.enabled
    reg.reset()
    reg.enable()
    yield reg
    reg.reset()
    reg.enabled = was


# ---------------------------------------------------------------------------
# obs.stats: exact percentiles + the streaming histogram
# ---------------------------------------------------------------------------

class TestStats:
    def test_percentile_matches_numpy(self):
        rng = np.random.RandomState(0)
        xs = list(rng.lognormal(-3, 1.5, size=257))
        for q in (0, 10, 50, 90, 99, 100):
            assert percentile(xs, q) == float(np.percentile(xs, q))

    def test_latency_summary_contract(self):
        xs = [0.5, 0.1, 0.9, 0.3]
        s = latency_summary(xs)
        assert set(s) == {"p50_s", "p90_s", "p99_s", "mean_s"}
        assert s["p50_s"] == float(np.percentile(xs, 50))
        assert s["p99_s"] == float(np.percentile(xs, 99))
        assert s["mean_s"] == pytest.approx(np.mean(xs))
        assert latency_summary([]) == {
            "p50_s": 0.0, "p90_s": 0.0, "p99_s": 0.0, "mean_s": 0.0
        }

    def test_streaming_histogram_quantiles(self):
        """p50/p90/p99 of a lognormal stream within the bucket-ratio error
        bound; count/sum/min/max exact."""
        rng = np.random.RandomState(3)
        xs = rng.lognormal(-4, 1.0, size=5000)    # latency-ish seconds
        h = StreamingHistogram()
        for v in xs:
            h.observe(float(v))
        assert h.count == len(xs)
        assert h.total == pytest.approx(xs.sum())
        assert h.min == xs.min() and h.max == xs.max()
        for q in (50, 90, 99):
            want = np.percentile(xs, q)
            assert h.quantile(q) == pytest.approx(want, rel=0.15), q

    def test_streaming_histogram_clamps_to_observed_extremes(self):
        h = StreamingHistogram()
        h.observe(0.25)
        assert h.quantile(0) == 0.25
        assert h.quantile(100) == 0.25
        assert h.summary()["count"] == 1.0

    def test_streaming_histogram_empty(self):
        h = StreamingHistogram()
        assert h.quantile(50) == 0.0
        assert h.summary()["count"] == 0.0 and h.summary()["min"] == 0.0


# ---------------------------------------------------------------------------
# Registry: disabled no-op contract, enabled metrics + span nesting
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_disabled_is_null(self):
        reg = Registry(enabled=False)
        reg.counter("c").inc(5)
        reg.gauge("g").set(2.0)
        reg.histogram("h").observe(1.0)
        with reg.span("s", x=1):
            reg.event("e")
        assert reg.record_span("r", 0.0, 1.0) is None
        snap = reg.snapshot()
        assert snap["counters"] == {} and snap["gauges"] == {}
        assert snap["histograms"] == {} and reg.events == []
        # The null singletons are shared (no per-call allocation); a span
        # is still a profiler annotation, which stores nothing here.
        assert reg.counter("a") is reg.counter("b")
        assert isinstance(reg.span("x"), jax.profiler.TraceAnnotation)

    def test_enabled_metrics(self):
        reg = Registry(enabled=True)
        reg.counter("c").inc()
        reg.counter("c").inc(2.5)
        reg.gauge("g").set(7)
        reg.histogram("h").observe(0.5)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 3.5
        assert snap["gauges"]["g"] == 7.0
        assert snap["histograms"]["h"]["count"] == 1.0

    def test_span_nesting_sets_parent(self):
        reg = Registry(enabled=True)
        with reg.span("outer"):
            with reg.span("inner", depth=1):
                pass
        inner, outer = reg.events       # inner closes (appends) first
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["parent"] == outer["id"]
        assert "parent" not in outer
        assert inner["t"] >= outer["t"]
        assert inner["dur"] <= outer["dur"] + 1e-9
        assert inner["attrs"] == {"depth": 1}

    def test_record_span_parents_and_ordering(self):
        reg = Registry(enabled=True)
        pid = reg.record_span("p", 1.0, 3.0, rid=9)
        cid = reg.record_span("c", 1.5, 2.0, parent=pid, rid=9)
        assert isinstance(pid, int) and isinstance(cid, int) and cid != pid
        assert reg.events[1]["parent"] == pid
        # Negative durations clamp (out-of-order stamps must not corrupt
        # the trace).
        reg.record_span("z", 5.0, 4.0)
        assert reg.events[2]["dur"] == 0.0

    def test_event_cap_drops_not_grows(self):
        reg = Registry(enabled=True, max_events=3)
        for i in range(5):
            reg.event("e", i=i)
        assert len(reg.events) == 3 and reg.events_dropped == 2

    def test_reset_clears(self):
        reg = Registry(enabled=True)
        reg.counter("c").inc()
        reg.event("e")
        reg.reset()
        assert reg.enabled and reg.events == []
        assert reg.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# Exporters: JSONL / Prometheus / span-chain checker
# ---------------------------------------------------------------------------

def _chain_registry():
    """A registry holding one complete request chain and one incomplete."""
    reg = Registry(enabled=True)
    reg.counter("serve.tokens_generated").inc(12)
    reg.gauge("serve.device.realized_drop_rate").set(0.25)
    reg.histogram("serve.ttft_s").observe(0.01)
    p = reg.record_span("request", 1.0, 2.0, rid=0)
    for name, (a, b) in zip(
        exporters.REQUEST_PHASES, [(1.0, 1.2), (1.2, 1.4), (1.4, 1.9), (1.9, 2.0)]
    ):
        reg.record_span(name, a, b, parent=p, rid=0)
    q = reg.record_span("request", 2.0, 3.0, rid=1)
    reg.record_span("request/queue", 2.0, 2.1, parent=q, rid=1)  # incomplete
    return reg


class TestExporters:
    def test_jsonl_roundtrip(self, tmp_path):
        reg = _chain_registry()
        path = tmp_path / "events.jsonl"
        exporters.write_jsonl(reg, str(path))
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert lines[0]["kind"] == "snapshot"
        assert lines[0]["counters"]["serve.tokens_generated"] == 12.0
        spans = [l for l in lines[1:] if l["kind"] == "span"]
        assert len(spans) == len(reg.events)
        assert {s["name"] for s in spans} >= {"request", *exporters.REQUEST_PHASES}

    def test_prometheus_text(self):
        text = exporters.prometheus_text(_chain_registry())
        assert "# TYPE serve_tokens_generated counter" in text
        assert "serve_tokens_generated 12.0" in text
        assert "serve_device_realized_drop_rate 0.25" in text
        assert '# TYPE serve_ttft_s summary' in text
        assert 'serve_ttft_s{quantile="0.50"}' in text
        assert "serve_ttft_s_count 1" in text

    def test_request_chain_rids(self):
        rids = exporters.request_chain_rids(_chain_registry())
        assert rids == {0}         # rid 1 is missing three phases

    def test_jax_profile_noop_without_dir(self):
        with exporters.jax_profile(None):
            pass


# ---------------------------------------------------------------------------
# Trace-time link taps
# ---------------------------------------------------------------------------

class TestLinkTaps:
    def test_apply_channel_mask_stats(self):
        """Tapped elems/dropped equal an independent recount from the
        masked output (kept positions are nonzero under compensation)."""
        from repro.core.link import apply_channel

        key = jax.random.PRNGKey(5)
        x = jnp.ones((4, 25), jnp.float32)
        with obs_device.tap_link_stats() as tap:
            y = apply_channel(key, x, 0.4)
            tot = {k: float(v) for k, v in tap.totals().items()}
        dropped = float(jnp.sum(np.asarray(y) == 0.0))
        assert tot["elems"] == x.size
        assert tot["dropped"] == dropped
        assert tot["fec_recovered"] == 0.0

    def test_untapped_is_silent(self):
        from repro.core.link import apply_channel

        assert not obs_device.tapping()
        apply_channel(jax.random.PRNGKey(0), jnp.ones((2, 2)), 0.5)
        assert not obs_device.tapping()

    def test_zero_loss_records_full_keep(self):
        from repro.core.comtune import LinkSpec, channel_link

        spec = LinkSpec(loss_rate=0.0)
        x = jnp.ones((1, 1, 50), jnp.float32)
        with obs_device.tap_link_stats() as tap:
            channel_link(jax.random.PRNGKey(0), x, spec)
            tot = {k: float(v) for k, v in tap.totals().items()}
        assert tot["elems"] == 50.0 and tot["dropped"] == 0.0

    def test_streamed_link_sums_per_position_rounds(self):
        """The streamed (vmapped) prefill link's totals equal the sum of
        the per-position draws taken individually."""
        from repro.core.comtune import LinkSpec, channel_link, streamed_channel_link

        spec = LinkSpec(loss_rate=0.35)
        key = jax.random.PRNGKey(9)
        msg = jnp.ones((1, 6, 40), jnp.float32)
        with obs_device.tap_link_stats() as tap:
            out = streamed_channel_link(key, msg, spec)
            tot = {k: float(v) for k, v in tap.totals().items()}
        assert tot["elems"] == msg.size
        # Independent recount from the realized zeros.
        assert tot["dropped"] == float(jnp.sum(np.asarray(out) == 0.0))

    def test_fec_recovery_count_hand_built_blocks(self):
        """k=4, m=2 RS over two blocks with a hand-built raw packet draw:
        block 1 loses 1 data packet but keeps 4-of-6 (recoverable -> +1),
        block 2 keeps 2-of-6 (unrecoverable -> +0)."""
        from repro.net.fec import FECSpec, fec_element_keep_jnp

        raw = jnp.asarray(
            [1, 1, 1, 0, 1, 0,      # block 1: data 3/4, total 4 >= k
             0, 0, 1, 1, 0, 0],     # block 2: data 2/4, total 2 < k
            jnp.float32,
        )

        class FixedChannel:
            def packet_keep_jnp(self, key, n):
                assert n == raw.size
                return raw

        spec = FECSpec(k=4, m=2)
        with obs_device.tap_link_stats() as tap:
            keep = fec_element_keep_jnp(
                jax.random.PRNGKey(0), FixedChannel(), 40, 5, spec
            )
            recovered = float(tap.totals()["fec_recovered"])
        assert recovered == 1.0
        # Block 1 fully recovered, block 2 delivers only its survivors.
        np.testing.assert_array_equal(
            np.asarray(keep).reshape(8, 5)[:, 0],
            [1, 1, 1, 1, 0, 0, 1, 1],
        )

    def test_unbalanced_stack_is_rejected(self):
        with pytest.raises(AssertionError):
            with obs_device.tap_link_stats():
                obs_device._STACK.append(obs_device.LinkTap())
        obs_device._STACK.clear()


# ---------------------------------------------------------------------------
# decode_read_bytes: traced twin == int analytic
# ---------------------------------------------------------------------------

class TestDecodeReadBytesJnp:
    def test_matches_int_analytic(self):
        cfg, _ = _setup()
        max_seq = 64
        valids = [1, 3, 17, 33, 64]
        for masked in (True, False):
            want = [
                cache_lib.decode_read_bytes(cfg, max_seq, v, masked=masked)
                for v in valids
            ]
            got = cache_lib.decode_read_bytes_jnp(
                cfg, max_seq, jnp.asarray(valids), masked=masked
            )
            np.testing.assert_array_equal(np.asarray(got), want)
            # Scalar form agrees too.
            for v, w in zip(valids, want):
                assert float(
                    cache_lib.decode_read_bytes_jnp(cfg, max_seq, v, masked=masked)
                ) == w


# ---------------------------------------------------------------------------
# Engine device counters vs the eager key-chain oracle
# ---------------------------------------------------------------------------

def _oracle_link_totals(cfg, params, jobs):
    """Replay each request's RNG chain through the exact serve-link closure
    (``lm.make_link_fn``) on zeros of the engine's message shapes: one
    streamed round over the PADDED bucket, then one (1, 1, d) round per
    generated token.  Mask draws depend only on (key, shape)."""
    from repro.models.common import dtype_of

    d, dt = cfg.d_model, dtype_of(cfg.dtype)
    tot = {"elems": 0.0, "dropped": 0.0, "fec_recovered": 0.0}
    for bucket, n_tokens, rkey in jobs:
        k, sub = jax.random.split(rkey)
        with obs_device.tap_link_stats() as tap:
            lm.make_link_fn(cfg, params["link"], sub, "serve")(
                jnp.zeros((1, bucket, d), dt)
            )
            for _ in range(n_tokens):
                k, sub = jax.random.split(k)
                lm.make_link_fn(cfg, params["link"], sub, "serve")(
                    jnp.zeros((1, 1, d), dt)
                )
            t = tap.totals()
        for name in tot:
            tot[name] += float(t[name])
    return tot


class TestDeviceCounterOracle:
    @pytest.mark.parametrize("channel", ["iid", "ge"])
    def test_link_counters_match_oracle(self, channel):
        cfg, params = _setup(channel=channel)
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=2, max_new=4, max_prompt=8, min_bucket=8)
        )
        key = jax.random.PRNGKey(21)
        spec = [(5, 3), (7, 2), (3, 4)]          # (prompt_len, tokens)
        for i, (L, T) in enumerate(spec):
            eng.submit(_prompt(i, L, cfg.vocab_size), T,
                       key=jax.random.fold_in(key, i))
        eng.run(params)
        got = eng.device_counters()
        jobs = [
            (eng.bucket_for(L), T, jax.random.fold_in(key, i))
            for i, (L, T) in enumerate(spec)
        ]
        want = _oracle_link_totals(cfg, params, jobs)
        np.testing.assert_allclose(got["link_elems"], want["elems"], rtol=1e-6)
        np.testing.assert_allclose(
            got["link_dropped"], want["dropped"], rtol=1e-6, atol=0.5
        )
        np.testing.assert_allclose(
            got["fec_recovered_packets"], want["fec_recovered"],
            rtol=1e-6, atol=0.5,
        )
        assert got["link_dropped"] > 0          # loss_rate 0.3 must drop
        assert 0.0 < got["realized_drop_rate"] < 1.0

    def test_valid_tokens_and_read_bytes_exact(self):
        cfg, params = _setup(loss_rate=0.0)
        pool = PoolConfig(max_slots=2, max_new=5, max_prompt=8, min_bucket=8)
        eng = ContinuousEngine(cfg, pool)
        key = jax.random.PRNGKey(4)
        spec = [(5, 3), (7, 5), (2, 1)]
        for i, (L, T) in enumerate(spec):
            eng.submit(_prompt(i, L, cfg.vocab_size), T,
                       key=jax.random.fold_in(key, i))
        eng.run(params)
        got = eng.device_counters()
        assert got["decode_steps"] == eng.steps
        # Live decode step t of a request sees valid = L + t + 1.
        want_valid = sum(
            sum(L + t + 1 for t in range(T)) for L, T in spec
        )
        assert got["valid_tokens"] == want_valid
        masked = cfg.attn_impl != "naive"
        want_bytes = sum(
            sum(
                cache_lib.decode_read_bytes(cfg, pool.max_seq, L + t + 1,
                                            masked=masked)
                for t in range(T)
            )
            for L, T in spec
        )
        assert got["decode_read_bytes"] == want_bytes

    def test_counters_before_first_run_are_zero(self):
        cfg, _ = _setup()
        eng = ContinuousEngine(cfg, PoolConfig(max_slots=2))
        got = eng.device_counters()
        assert got["realized_drop_rate"] == 0.0
        assert all(v == 0.0 for v in got.values())


# ---------------------------------------------------------------------------
# Obs on/off never changes the compiled programs or the tokens
# ---------------------------------------------------------------------------

class TestObsProgramInvariance:
    @pytest.mark.parametrize("channel", ["iid", "ge"])
    def test_enabled_registry_token_identity_and_compiles(
        self, channel, global_registry_enabled
    ):
        """With the registry ENABLED: compiles == num_buckets + 1 and the
        greedy outputs still equal the per-request reference."""
        cfg, params = _setup(channel=channel)
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=2, max_new=4, max_prompt=16, min_bucket=8)
        )
        key = jax.random.PRNGKey(13)
        lengths = [5, 12, 7]
        reqs = [
            eng.submit(_prompt(i, L, cfg.vocab_size), 3,
                       key=jax.random.fold_in(key, i))
            for i, L in enumerate(lengths)
        ]
        eng.run(params)
        assert eng.compiles == eng.num_buckets + 1
        for i, (L, req) in enumerate(zip(lengths, reqs)):
            ref, _ = generate_reference(
                params, cfg, jnp.asarray(_prompt(i, L, cfg.vocab_size))[None],
                3, key=jax.random.fold_in(key, i),
            )
            np.testing.assert_array_equal(np.asarray(ref)[0], req.tokens)

    def test_toggling_obs_adds_zero_compiles(self):
        """Enable the registry mid-run: more traffic on warm buckets must
        not build a single new program (obs state is carried either way)."""
        reg = obs.registry()
        assert not reg.enabled
        cfg, params = _setup()
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=2, max_new=3, max_prompt=8, min_bucket=8)
        )
        key = jax.random.PRNGKey(2)
        eng.submit(_prompt(0, 5, cfg.vocab_size), 2, key=key)
        eng.run(params)
        warm = eng.compiles
        # prompts/keys precomputed: _prompt's randint traces a throwaway
        # program per fresh length, which the compile guard must not see
        traffic = [
            (_prompt(1 + i, 4 + i, cfg.vocab_size),
             jax.random.fold_in(key, i))
            for i in range(3)
        ]
        reg.enable()
        try:
            with no_recompile(engines=(eng,)):
                for prompt, k in traffic:
                    eng.submit(prompt, 2, key=k)
                eng.run(params)
            assert eng.compiles == warm
            assert eng.traces == warm
        finally:
            reg.disable()
            reg.reset()


# ---------------------------------------------------------------------------
# Request lifecycle spans + timing granularity
# ---------------------------------------------------------------------------

class TestRequestLifecycle:
    def test_span_chain_and_timestamp_ordering(self, global_registry_enabled):
        reg = global_registry_enabled
        cfg, params = _setup()
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=2, max_new=4, max_prompt=8, min_bucket=8)
        )
        key = jax.random.PRNGKey(6)
        reqs = [
            eng.submit(_prompt(i, 4 + i, cfg.vocab_size), 3,
                       key=jax.random.fold_in(key, i))
            for i in range(3)
        ]
        eng.run(params)
        for r in reqs:
            assert r.t_submit <= r.t_admit <= r.t_first_token
            assert r.t_first_token <= r.t_done <= r.t_retire
            assert r.ttft_s > 0 and r.tpot_s >= 0 and r.e2e_s >= r.ttft_s
        # Every request closed a complete submit->retire chain.
        assert exporters.request_chain_rids(reg) == {r.rid for r in reqs}
        snap = reg.snapshot()
        assert snap["counters"]["serve.requests_submitted"] == 3.0
        assert snap["counters"]["serve.requests_retired"] == 3.0
        assert snap["counters"]["serve.tokens_generated"] == 9.0
        assert snap["histograms"]["serve.ttft_s"]["count"] == 3.0
        # run() published the device counters as gauges.
        assert "serve.device.realized_drop_rate" in snap["gauges"]

    def test_request_stats_summary_keys(self):
        cfg, params = _setup()
        eng = ContinuousEngine(
            cfg, PoolConfig(max_slots=2, max_new=3, max_prompt=8, min_bucket=8)
        )
        eng.submit(_prompt(0, 5, cfg.vocab_size), 2)
        eng.run(params)
        s = eng.stats()
        for k in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "e2e_mean_s",
                  "requests"):
            assert k in s, k
        assert s["requests"] == 1.0 and s["e2e_mean_s"] > 0


# ---------------------------------------------------------------------------
# Disabled-registry overhead
# ---------------------------------------------------------------------------

class TestDisabledOverhead:
    def test_null_path_cost_is_negligible(self):
        """~32 registry touches per decode step must cost well under 2% of
        even a fast (5 ms) step: bound the per-op null-path cost."""
        reg = Registry(enabled=False)
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            reg.counter("c").inc()
            reg.gauge("g").set(1.0)
            reg.histogram("h").observe(1.0)
            with reg.span("s"):
                pass
        per_op = (time.perf_counter() - t0) / (4 * n)
        assert per_op < 2e-6, f"null-path op cost {per_op*1e9:.0f} ns"
        assert 32 * per_op < 0.02 * 0.005      # 32 ops vs 2% of a 5 ms step


# ---------------------------------------------------------------------------
# Train metrics carry the link stats
# ---------------------------------------------------------------------------

class TestTrainLinkMetrics:
    def test_train_step_metrics_have_link_stats(self):
        from repro.launch.steps import make_train_step
        from repro.optim import AdamConfig, init_adam

        cfg, params = _setup(loss_rate=0.0)
        adam_cfg = AdamConfig(lr=1e-3)
        opt = init_adam(params, adam_cfg)
        tokens = jnp.zeros((2, 8), jnp.int32)
        for mode, expect_draws in (("train", True), ("off", False)):
            step = jax.jit(make_train_step(cfg, adam_cfg, link_mode=mode))  # noqa: RPA001 — one compile per link_mode under test
            _, _, metrics = step(params, opt, {"tokens": tokens},
                                 jax.random.PRNGKey(0))
            for k in ("link_elems", "link_dropped", "fec_recovered_packets"):
                assert k in metrics, (mode, k)
            elems = float(metrics["link_elems"])
            assert (elems > 0) == expect_draws, mode


# ---------------------------------------------------------------------------
# Simulator: shared stats + registry export
# ---------------------------------------------------------------------------

class TestSimulatorObs:
    def test_sim_registry_export(self, global_registry_enabled):
        from repro.net import SimConfig, run_sim

        reg = global_registry_enabled
        rep = run_sim(SimConfig(n_clients=3, duration_s=1.5, seed=2))
        assert rep.served > 0
        snap = reg.snapshot()
        assert snap["counters"]["sim.requests_arrived"] == rep.arrived
        assert snap["counters"]["sim.requests_served"] == rep.served
        assert snap["histograms"]["sim.latency_s"]["count"] == rep.served
        names = [e["name"] for e in reg.events]
        assert names.count("sim.request") == rep.served
        assert names.count("sim.uplink") == rep.served
        assert "sim.run" in names
        # Uplink spans sit inside their request span on the sim clock.
        by_id = {e["id"]: e for e in reg.events if e["kind"] == "span"}
        for e in reg.events:
            if e["name"] == "sim.uplink":
                parent = by_id[e["parent"]]
                assert parent["name"] == "sim.request"
                assert e["t"] >= parent["t"] - 1e-9
                assert e["t"] + e["dur"] <= parent["t"] + parent["dur"] + 1e-9

    def test_uplink_start_is_stamped(self):
        from repro.net import SimConfig, run_sim

        calls = []

        def fake_engine(batch):
            calls.extend(batch)
            return 0.01

        run_sim(
            SimConfig(n_clients=1, n_packets=4, duration_s=1.0,
                      min_delivered_fraction=0.0),
            arrivals=[(0.0, 0), (0.0, 0)],
            engine=fake_engine,
        )
        # Second request queued behind the busy radio: its uplink starts
        # when the first one's finishes, not at arrival.
        a, b = sorted(calls, key=lambda r: r.rid)
        assert a.t_uplink_start == pytest.approx(a.t_arrival)
        assert b.t_uplink_start == pytest.approx(a.t_uplink_done)

    def test_sim_disabled_stays_silent(self):
        from repro.net import SimConfig, run_sim

        reg = obs.registry()
        assert not reg.enabled
        before = len(reg.events)
        rep = run_sim(SimConfig(n_clients=2, duration_s=1.0, seed=0))
        assert rep.latency_p50_s >= 0.0
        assert len(reg.events) == before


# ---------------------------------------------------------------------------
# Router observability: per-shard occupancy gauges vs the host oracle
# ---------------------------------------------------------------------------

class TestRouterGauges:
    """The sharded router publishes per-shard occupancy at the existing
    host sync points (admission / preemption / completion) plus a final
    refresh in run().  The gauges must equal the host-side oracle — the
    same public probes (`free_slot_count` / `free_block_count()`) the
    placement policy itself reads."""

    def test_shard_gauges_and_placement_counters(self, global_registry_enabled):
        from repro.serve import ShardedEngine

        reg = global_registry_enabled
        cfg, params = _setup()
        dev = jax.devices()[0]
        eng = ShardedEngine(
            cfg, PoolConfig(max_slots=2, max_new=8, max_prompt=16),
            devices=[dev, dev],
        )
        base = jax.random.PRNGKey(5)
        reqs = [
            eng.submit(_prompt(i, n, cfg.vocab_size), 4,
                       key=jax.random.fold_in(base, i))
            for i, n in enumerate((5, 9, 12))
        ]
        eng.step(params)
        # Mid-flight: occupancy gauges reflect the state after the last
        # admission, which decode does not change until a completion.
        snap = reg.snapshot()
        assert eng.active == 3
        for i, sh in enumerate(eng.shards):
            assert snap["gauges"][f"serve.shard_free_slots.{i}"] == float(
                sh.free_slot_count
            )
            assert snap["gauges"][f"serve.shard_free_blocks.{i}"] == float(
                sh.free_block_count()
            )
        done = eng.run(params)
        assert len(done) == len(reqs)
        snap = reg.snapshot()
        # Terminal refresh: pool fully idle again.
        assert snap["gauges"]["router.queue_depth"] == 0.0
        for i in range(eng.num_shards):
            assert snap["gauges"][f"serve.shard_free_slots.{i}"] == float(
                eng.pool.max_slots
            )
        # Placement counters == the router's own placement ledger, and
        # every admission was counted exactly once (no preemptions here).
        assert snap["counters"]["router.placements"] == float(len(reqs))
        for i in range(eng.num_shards):
            assert snap["counters"].get(
                f"router.placements.shard{i}", 0.0
            ) == float(eng.placement_counts[i])
        assert snap["counters"]["serve.requests_submitted"] == float(len(reqs))
        # run() published the shard-summed device counters as gauges,
        # with the drop rate re-derived from the summed totals.
        host = eng.device_counters()
        for k, v in host.items():
            assert snap["gauges"][f"serve.device.{k}"] == pytest.approx(v)
        assert snap["gauges"]["serve.device.link_elems"] == pytest.approx(
            sum(sh.device_counters()["link_elems"] for sh in eng.shards)
        )
