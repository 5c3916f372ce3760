"""The serving engine's own tracing: host spans on the profiler's timeline,
device scopes in the compiled programs' op metadata, and the rule that
observing the engine never changes its schedule.

* ``ContinuousEngine`` opens ``serve.step`` / ``serve.submit`` /
  ``serve.admit`` / ``serve.decode`` / ``serve.sync`` / ``serve.harvest``
  through ``obs.registry().span``, which always writes a profiler
  annotation, registry on or off.
* The decode and prefill programs carry ``jax.named_scope`` names
  (``di_*``) in every op's ``op_name``; ``stack_split`` marks the weight
  slices of a forward without a cache.
* With the registry enabled the engine makes exactly the device syncs it
  makes with it off, and builds the same programs.
"""

import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import ARCHITECTURES
from repro.models import lm
from repro.serve import ContinuousEngine, PoolConfig
from repro.serve import continuous as continuous_lib

SERVE_SPANS = ("serve.step", "serve.submit", "serve.admit", "serve.decode",
               "serve.sync", "serve.harvest")


@pytest.fixture(scope="module")
def model():
    """The reduced qwen1.5-0.5b with the link after its first unit, so the
    device half, the server half and the copies at the split all exist."""
    cfg = ARCHITECTURES["qwen1.5-0.5b"].reduced()
    cfg = cfg.with_updates(
        link=dataclasses.replace(cfg.link, split_after_units=1, loss_rate=0.3)
    )
    assert 0 < cfg.link.split_after_units < cfg.resolved_num_units
    return cfg, lm.init_lm(jax.random.PRNGKey(0), cfg)


def _prompt(i, length, vocab):
    return np.random.RandomState(i).randint(0, vocab, size=(length,)).astype(np.int32)


def _host_spans(trace_dir):
    """(name, start_ns, end_ns) of every ``serve.*`` span on a host plane."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name in SERVE_SPANS]
    return out


class TestEngineSpans:
    def test_one_step_writes_every_serve_span(self, model, tmp_path):
        """A tick that admits (reading a finished row first) and completes
        a request, under a CPU profiler session: each ``serve.*`` span is
        on the host timeline, and all but ``serve.submit`` inside the
        tick's ``serve.step``."""
        cfg, params = model
        eng = ContinuousEngine(cfg, PoolConfig(max_slots=1, max_new=2, max_prompt=8))
        key = jax.random.PRNGKey(3)
        eng.submit(_prompt(0, 5, cfg.vocab_size), 1, key=key)
        eng.step(params)                  # compiles; the request completes
        assert eng._pending_harvest
        prompt = _prompt(1, 6, cfg.vocab_size)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            eng.submit(prompt, 1, key=jax.random.fold_in(key, 1))
            eng.step(params)
        spans = _host_spans(str(tmp_path))
        assert sorted(n for n, _, _ in spans) == sorted(SERVE_SPANS)
        at = {n: (s, e) for n, s, e in spans}
        inside = lambda a, b: at[b][0] <= at[a][0] and at[a][1] <= at[b][1]
        for name in ("serve.admit", "serve.decode", "serve.sync", "serve.harvest"):
            assert inside(name, "serve.step"), name
        assert inside("serve.harvest", "serve.admit")
        assert at["serve.submit"][1] <= at["serve.step"][0]

    def test_spans_reach_the_registry_when_enabled(self, model):
        cfg, params = model
        reg = obs.registry()
        reg.reset()
        reg.enable()
        try:
            eng = ContinuousEngine(cfg, PoolConfig(max_slots=2, max_new=2, max_prompt=8))
            eng.submit(_prompt(2, 4, cfg.vocab_size), 2)
            eng.run(params)
            names = {e["name"] for e in reg.events}
        finally:
            reg.disable()
            reg.reset()
        assert set(SERVE_SPANS) <= names


def _scopes(hlo_text):
    """The ``di_*`` / ``stack_*`` scope names in a program's ``op_name``s."""
    scopes = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        for c in path.split("/"):
            m = re.fullmatch(r"(?:vmap\()*((?:di|stack)_\w+?)\)*", c)
            if m:
                scopes.add(m.group(1))
    return scopes


class TestDeviceScopes:
    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_programs_carry_scopes(self, model, program):
        """Every ``di_*`` scope is in the serving programs, and neither
        ``stack_*`` scope: with a cache the stack runs over the whole
        stacked weights and caches, so nothing is sliced or merged at the
        split.  The forward without a cache still slices its weights."""
        cfg, params = model
        eng = ContinuousEngine(cfg, PoolConfig(max_slots=2, max_new=2, max_prompt=8))
        eng.submit(_prompt(3, 5, cfg.vocab_size), 2)
        eng.run(params)
        exe = eng.decode_executable if program == "decode" else eng._prefill_fns[8]
        scopes = _scopes(exe.as_text())
        want = {"di_device_half", "di_link", "di_server_half", "di_head",
                "di_sample"}
        assert want <= scopes, want - scopes
        assert not scopes & {"stack_split", "stack_merge"}, scopes
        tokens = _prompt(4, 8, cfg.vocab_size)[None]
        train = jax.jit(lambda p, t: lm.forward(
            p, t, cfg, link_key=jax.random.PRNGKey(0), link_mode="train"
        )[0]).lower(params, tokens).compile()
        assert {"di_device_half", "di_link", "di_server_half",
                "stack_split"} <= _scopes(train.as_text())


class TestScheduleUnchanged:
    def _syncs(self, model, monkeypatch, enabled):
        """Every device sync the engine makes serving a fixed mix, in
        order: completion-step ``block_until_ready`` and harvest copies."""
        cfg, params = model
        seen = []
        jax_block = jax.block_until_ready

        def blocking(x):
            seen.append("block_until_ready")
            return jax_block(x)

        class Numpy:
            def __getattr__(self, name):
                return getattr(np, name)

            def asarray(self, a, *args, **kw):
                if isinstance(a, jax.Array):
                    seen.append("asarray")
                return np.asarray(a, *args, **kw)

        monkeypatch.setattr(jax, "block_until_ready", blocking)
        monkeypatch.setattr(continuous_lib, "np", Numpy())
        reg = obs.registry()
        reg.reset()
        reg.enabled = enabled
        try:
            eng = ContinuousEngine(cfg, PoolConfig(max_slots=2, max_new=4, max_prompt=16))
            key = jax.random.PRNGKey(11)
            reqs = [eng.submit(_prompt(10 + i, n, cfg.vocab_size), t,
                               key=jax.random.fold_in(key, i))
                    for i, (n, t) in enumerate([(5, 3), (12, 1), (7, 4), (3, 2)])]
            eng.run(params)
        finally:
            reg.disable()
            reg.reset()
            monkeypatch.undo()
        assert eng.compiles == eng.num_buckets + 1
        for r in reqs:
            assert r.t_admit <= r.t_first_token <= r.t_done <= r.t_retire
        return seen, [r.tokens.tolist() for r in reqs]

    def test_registry_adds_no_device_sync(self, model, monkeypatch):
        off, tokens_off = self._syncs(model, monkeypatch, enabled=False)
        on, tokens_on = self._syncs(model, monkeypatch, enabled=True)
        assert off == on
        assert "block_until_ready" in off and "asarray" in off
        assert tokens_off == tokens_on
