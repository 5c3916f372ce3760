"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and when the control (the reference
in float8, the precision below the configurations' bfloat16) is put in
the program's place.  The chip's check is skipped; everything else runs."""

import jax
import numpy as np
import pytest

from bench import reference, train
from test_bench_cells import run_cell

SERVE_CELLS = ["tiny-serve-open", "tiny-serve-closed"]


def _no_result_or_false(rc, line):
    assert rc == 0 and line is not None
    assert line["correct"] is False, line


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_token_altered_where_produced(tiny_root, capsys, monkeypatch, cell):
    from repro.serve import continuous

    real = continuous.ContinuousEngine.take_finished

    def altered(self):
        done = real(self)
        for r in done:
            t = np.array(r.tokens)
            t[len(t) // 2] = (t[len(t) // 2] + 1) % self.cfg.vocab_size
            r.tokens = t
        return done

    monkeypatch.setattr(continuous.ContinuousEngine, "take_finished", altered)
    _no_result_or_false(*run_cell(tiny_root, cell, capsys)[:2])


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_step_returns_state_unchanged(tiny_root, capsys, monkeypatch, cell):
    from repro.serve import continuous

    monkeypatch.setattr(continuous.ContinuousEngine, "_make_decode_step",
                        lambda self: (lambda params, state: state))
    _no_result_or_false(*run_cell(tiny_root, cell, capsys)[:2])


def _broken_epoch(monkeypatch, fault):
    from repro.launch import steps

    real = steps.make_train_epoch

    def make(cfg, adam, **kw):
        epoch = real(cfg, adam, jit=False, **kw)

        def broken(params, opt, batches, key):
            if fault == "unchanged":
                _, _, key2, metrics = epoch(params, opt, batches, key)
                return params, opt, key2, metrics
            half = {k: v[:, : v.shape[1] // 2] for k, v in batches.items()}
            return epoch(params, opt, half, key)

        return jax.jit(broken, donate_argnums=(0, 1))

    monkeypatch.setattr(steps, "make_train_epoch", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_faults(tiny_root, capsys, monkeypatch, fault):
    _broken_epoch(monkeypatch, fault)
    _no_result_or_false(*run_cell(tiny_root, "tiny-train", capsys)[:2])


def test_train_control_in_the_programs_place(tiny_root, capsys, monkeypatch):
    """The reference in float8, its readings handed over as the program's."""
    real_first = train.first_epoch

    def control(compiled, params, opt, key, first, conf, seed):
        params, opt, key, _ = real_first(compiled, params, opt, key, first, conf, seed)
        k = first.shape[0]
        return params, opt, key, reference.train_steps(
            conf, seed, first, train.train_key(seed), {"lr": 3e-4, "b1": 0.9, "b2": 0.999,
                                                      "eps": 1e-8, "clip_norm": 1.0},
            k, prec="fp8")

    monkeypatch.setattr(train, "first_epoch", control)
    _no_result_or_false(*run_cell(tiny_root, "tiny-train", capsys)[:2])


def test_serve_control_fails_the_limit(tiny_root, capsys, monkeypatch):
    """At each position of the served prompts and tokens, the token that the
    float8 reference puts first lies further below the reference's best
    than the cell's limit allows; the program's own tokens do not."""
    from bench import serve
    from tinycells import CELLS

    seen = {}
    real = serve.check_sample

    def with_control(conf, traffic, seed, sample, keys, control=False, gap=None):
        seen.update(real(conf, traffic, seed, sample, keys, control=True, gap=gap))
        return seen

    monkeypatch.setattr(serve, "check_sample", with_control)
    # The open loop's sample is fixed by the seed (due times do not depend
    # on the server), so this seed's control reading (0.020) is known.
    rc, line, _ = run_cell(tiny_root, "tiny-serve-open", capsys, seed=2**31 + 5)
    limit = CELLS["tiny-serve-open"][2]["served_gap"]
    assert rc == 0 and line["correct"] is True, line
    assert seen["served"] <= limit < seen["control"], seen
