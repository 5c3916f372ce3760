"""Router near-ties in the check of ``correct`` (``reference._TieSearch``),
on a tiny routed-expert configuration (``router_arch.py``).

Served tokens are drawn here from the f32 reference itself, greedily, with
one expert choice forced the other way at a near-tie: what the bfloat16
program may do where two experts' scores lie within its rounding.  The
check has to explain that flip, and no more than that: a token altered
where no near-tie is, or a sample that needs more flips than
``max_flips``, still fails."""

import functools
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model, reference
from test_bench_cells import _add_cell, run_cell
from tinycells import CELLS, ROUTER, ROUTER_TIE, build_root

SEED = 2**31 + 11
P, N = 8, 12                       # prompt and served tokens of a request
LENGTH = P + N + 4                 # positions, padded
LIMIT = CELLS["tiny-serve-open"][2]["served_gap"]
GAP = {"limit": LIMIT, "router_tie": ROUTER_TIE}
LOSSLESS = reference.ge_params(0.0)    # every element kept: the link only quantises


@pytest.fixture(scope="module")
def conf(tmp_path_factory):
    root = build_root(tmp_path_factory.mktemp("router"))
    shutil.copy(Path(__file__).with_name("router_arch.py"),
                root / "bench" / "arch" / "MadeUpRouterForCausalLM.py")
    _add_cell(root, "tiny-router", "MadeUpRouterForCausalLM", "tiny-router-serve", LIMIT,
              ROUTER_TIE, **ROUTER)
    return harness.find_cell(root, "tiny-router-serve")[2]


@functools.lru_cache(maxsize=None)
def _hooks(conf_json):
    conf = json.loads(conf_json)
    mod = model.arch(conf)
    return (jax.jit(lambda x, w, f: mod.layer_forward(x, w, conf, "f32", 0, flips=f)),
            jax.jit(lambda x, w: mod.route_margins(x, w, conf, 0)))


def _forward(conf, tokens, flips=()):
    """The reference's logits (L, V) over one row, and each layer's router
    margins (L,), with the expert choice flipped at each (layer, position)
    in ``flips``: written here from the module's hooks, apart from the
    search under test."""
    m = model.dims(conf)
    layer, margins_of = _hooks(json.dumps(conf))
    outer = reference.outer_at(conf, SEED)
    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(outer["embed"], jnp.asarray(tokens)[None], axis=0)
        for i in range(m["layers"]):
            if i == m["split"]:
                x = reference.serve_link(x, jnp.ones_like(x), conf, 0.0)
            w = reference.layer_at(conf, SEED, i)
            margins.append(np.asarray(margins_of(x, w))[0])
            mask = np.zeros((1, len(tokens)), bool)
            mask[0, [q for li, q in flips if li == i]] = True
            x = layer(x, w, jnp.asarray(mask))
        logits = reference.head_logits(x, outer, conf, "f32")[0]
    return np.asarray(logits), margins


def _greedy(conf, prompt, flips=()):
    """The sequence (prompt and served tokens but the last, padded) and the
    N served tokens that the reference picks greedily under ``flips``."""
    seq = np.zeros((LENGTH,), np.int32)
    seq[:P] = prompt
    served = []
    for t in range(N):
        logits, _ = _forward(conf, seq, flips)
        served.append(int(np.argmax(logits[P - 1 + t])))
        if t < N - 1:
            seq[P + t] = served[-1]
    return seq, np.array(served, np.int32)


def _gap(logits, q, token):
    return float(logits[q].max() - logits[q][token])


@pytest.fixture(scope="module")
def forced(conf):
    """A prompt, its plain greedy tokens, and a near-tie (layer, position)
    whose flip changes the served token there by more than the limit: the
    flipped layer has the smallest margin under ``tie_margin`` there."""
    rng = np.random.default_rng(17)
    served_at = range(P - 1, P - 1 + N)
    for _ in range(12):
        prompt = rng.integers(0, conf["vocab_size"], P).astype(np.int32)
        seq, plain = _greedy(conf, prompt)
        _, margins = _forward(conf, seq)
        for q in served_at:
            near = sorted((mg[q], i) for i, mg in enumerate(margins)
                          if mg[q] < ROUTER_TIE["tie_margin"])
            if not near:
                continue
            flip = (near[0][1], q)
            seq_f, served_f = _greedy(conf, prompt, [flip])
            if _gap(_forward(conf, seq_f)[0], q, served_f[q - P + 1]) > LIMIT:
                return {"prompt": prompt, "plain": plain, "flipped": served_f, "flip": flip,
                        "margins": margins}
    raise AssertionError("no near-tie flip changes a served token at this size")


def _item(prompt, tokens):
    return {"prompt": prompt, "tokens": np.asarray(tokens, np.int32),
            "prefill_key": np.zeros((2,), np.uint32),
            "round_keys": np.zeros((len(tokens) - 1, 2), np.uint32)}


def _gaps(conf, items, gap=GAP, control=False):
    return reference.serve_gaps(conf, SEED, items, 0.0, LOSSLESS, (len(items), LENGTH),
                                with_control=control, gap=gap)


def test_near_tie_flip_is_resolved(conf, forced):
    """Tokens served with one expert choice flipped at a near-tie read a
    plain gap over the limit; the search takes that one flip back and the
    resolved gap is within it."""
    out = _gaps(conf, [_item(forced["prompt"], forced["flipped"])])
    assert out["served_plain"] > LIMIT >= out["served"], out
    assert out["router_flips"] == 1, out


def test_plain_tokens_need_no_flip(conf, forced):
    out = _gaps(conf, [_item(forced["prompt"], forced["plain"])])
    assert out["served_plain"] == out["served"] <= LIMIT and out["router_flips"] == 0, out


def test_altered_token_without_near_tie_fails(conf, forced):
    """A served token altered where no router layer is near a tie: no flip
    is tried, and the gap stands."""
    tie = ROUTER_TIE["tie_margin"]
    clear = [q for q in range(P - 1, P - 1 + N)
             if all(mg[q] >= tie for mg in forced["margins"])]
    assert clear
    q = clear[-1]
    tokens = np.array(forced["plain"][: q - P + 2])
    tokens[-1] = (tokens[-1] + 1) % conf["vocab_size"]
    out = _gaps(conf, [_item(forced["prompt"], tokens)])
    assert out["served"] > LIMIT and out["router_flips"] == 0, out


@pytest.mark.parametrize("max_flips", [1, 2])
def test_flips_are_capped_over_the_sample(conf, forced, max_flips):
    """Two requests that each need the one flip: within a cap of two both
    are explained; under a cap of one the second one's gap stands."""
    item = _item(forced["prompt"], forced["flipped"])
    out = _gaps(conf, [item, item], gap=dict(GAP, router_tie=dict(ROUTER_TIE,
                                                                    max_flips=max_flips)))
    assert out["router_flips"] == max_flips, out
    assert (out["served"] <= LIMIT) == (max_flips == 2), out


def test_control_goes_through_the_search(conf, forced):
    """The float8 control's picks are resolved by the same search, on a
    budget of their own, and read above the limit after it."""
    out = _gaps(conf, [_item(forced["prompt"], forced["flipped"])], control=True)
    assert {"control", "control_plain", "control_flips"} <= set(out)
    assert out["control"] <= out["control_plain"]
    assert out["control"] > LIMIT, out


def test_no_router_tie_no_search(tiny_root, capsys, monkeypatch):
    """A Qwen2 cell whose limits give no ``router_tie`` never enters the
    search, and its check reports no flips."""
    def refuse(*a, **k):
        raise AssertionError("the router-tie search ran")

    monkeypatch.setattr(reference, "_TieSearch", refuse)
    rc, line, err = run_cell(tiny_root, "tiny-serve-open", capsys)
    assert rc == 0 and line["correct"] is True, line
    assert set(line["check"]["served_gap"]) == {"value", "limit"}
    assert "router_flips" not in err


def test_router_cell_reports_its_flips(tiny_root, capsys):
    """A routed-expert cell's check line carries the plain gap and the
    flips beside the resolved gap, in the result line and on stderr."""
    shutil.copy(Path(__file__).with_name("router_arch.py"),
                tiny_root / "bench" / "arch" / "MadeUpRouterForCausalLM.py")
    _add_cell(tiny_root, "tiny-router", "MadeUpRouterForCausalLM", "tiny-router-serve", LIMIT,
              ROUTER_TIE, **ROUTER)
    rc, line, err = run_cell(tiny_root, "tiny-router-serve", capsys)
    assert rc == 0 and line["correct"] is True, line
    check = line["check"]["served_gap"]
    assert set(check) == {"value", "limit", "plain", "router_flips"}, check
    assert check["value"] <= check["plain"] and check["router_flips"] >= 0
    assert " router_flips " in err.strip().splitlines()[-1]
