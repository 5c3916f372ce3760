"""Training cells: COMtune fine-tuning through ``launch.steps.make_train_epoch``
(the scan epoch ``launch.train.train`` runs), one dispatch per epoch of
``steps_per_dispatch`` steps, on a synthetic token stream from the seed.

Set-up builds the compiled epoch with its state and drives it through
the first epoch; the window then drives the same object.  The reference
follows that first epoch's steps.
"""

from __future__ import annotations

import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import model, reference
from bench.harness import Record, annotate, profile_window

TRACE_AT, TRACE_S = 0.3, 3.0


def canonical(conf: dict, tree) -> dict:
    """The program's parameter-shaped tree under the reference's names, in
    float32, layers stacked on the leading axis."""
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  model.arch(conf).canonical(tree))


def feed(seed: int, vocab: int, k: int, b: int, s: int):
    """Batches (k, b, s) of uniformly drawn token ids: every row differs."""
    rng = np.random.default_rng(seed ^ 0xFEED)
    while True:
        yield rng.integers(0, vocab, (k, b, s), dtype=np.int32)


def train_key(seed: int):
    return jax.random.fold_in(model.seed_key(seed), 0x7A1)


def adam_config(opt: dict):
    from repro.optim import AdamConfig

    return AdamConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                      grad_clip_norm=opt["clip_norm"], state_dtype=opt["state_dtype"])


def build(conf: dict, traffic: dict, seed: int):
    """The compiled epoch and its state at step 0, with the first batch."""
    from repro.launch.steps import make_train_epoch
    from repro.optim import init_adam

    cfg = model.program_config(conf, traffic["link"], remat=traffic["remat"])
    adam = adam_config(traffic["optimizer"])
    params = model.program_params(conf, seed)
    model.check_layout(params, cfg)
    opt = init_adam(params, adam)
    epoch = make_train_epoch(cfg, adam, link_mode="train")
    k, b, s = traffic["steps_per_dispatch"], traffic["batch"], traffic["seq"]
    batches = feed(seed, conf["vocab_size"], k, b, s)
    first = next(batches)
    key = train_key(seed)
    compiled = epoch.lower(params, opt, {"tokens": jnp.asarray(first)}, key).compile()
    return compiled, params, opt, key, batches, first


def first_epoch(compiled, params, opt, key, first, conf, seed):
    """Drive the compiled epoch through its first steps and read what the
    reference is compared with: each step's loss, Adam's first moment and
    the parameters' change, per leaf and layer, and the first moment itself
    (on the host, under the reference's names)."""
    params, opt, key, metrics = compiled(params, opt, {"tokens": jnp.asarray(first)}, key)
    start = model.program_params(conf, seed)
    norms = jax.jit(lambda p, s, mu: (reference._norms(canonical(conf, mu)),
                                      reference._norms(jax.tree_util.tree_map(
                                          jnp.subtract, canonical(conf, p),
                                          canonical(conf, s)))))
    moment, change = norms(params, start, opt.mu)
    out = {"loss": [float(v) for v in np.asarray(metrics["loss"])],
           "moment_tree": jax.device_get(jax.jit(lambda mu: canonical(conf, mu))(opt.mu)),
           "moment": {k: np.asarray(v, np.float64) for k, v in moment.items()},
           "change": {k: np.asarray(v, np.float64) for k, v in change.items()}}
    del start
    return params, opt, key, out


def diff_gaps(got: dict, ref: dict) -> list:
    """Per leaf and layer: the norm of the difference of two canonical
    trees over the larger of the reference's norm and the median leaf's."""
    def rows(tree):
        for name, a in tree.items():
            if name == "layers":
                yield from ((f"{k}.{i}", v[i]) for k, v in a.items() for i in range(len(v)))
            else:
                yield name, a
    ref_rows, got_rows = dict(rows(ref)), dict(rows(got))
    norms = {k: float(np.linalg.norm(v.ravel())) for k, v in ref_rows.items()}
    med = statistics.median(norms.values())
    return [float(np.linalg.norm((got_rows[k] - v).ravel())) / max(norms[k], med)
            for k, v in ref_rows.items()]


def leaf_gap(got: dict, ref: dict, ref_grad: dict) -> float:
    """Worst leaf (per layer) of |norm(program) - norm(reference)| over the
    larger of the reference's norm and the median leaf's.  Leaves whose
    first reference gradient is under a thousandth of the median leaf's
    (a key's bias under softmax, the link's clip range) move by round-off
    alone and are left out."""
    gmed = statistics.median(float(v) for a in ref_grad.values() for v in a)
    keep = {k: ref_grad[k] >= 1e-3 * gmed for k in ref}
    med = statistics.median(float(v) for k, a in ref.items() for v, ok in zip(a, keep[k]) if ok)
    worst = 0.0
    for k, r in ref.items():
        g = np.asarray(got[k], np.float64)
        gap = np.abs(g - r) / np.maximum(r, med)
        worst = max(worst, float(np.max(np.where(keep[k], gap, 0.0))))
    return worst


def compare(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` holds to their limits.  The gaps of norms
    are second order in rounding errors that are independent element by
    element, which cancel in a norm; ``moment_diff``, the median leaf's
    norm of the first moment's difference, is first order in them."""
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"])),
        "moment_gap": leaf_gap(prog["moment"], ref["moment"], ref["grad"]),
        "change_gap": leaf_gap(prog["change"], ref["change"], ref["grad"]),
        "moment_diff": statistics.median(diff_gaps(prog["moment_tree"], ref["moment_tree"])),
    }


def run(cell) -> Record:
    conf, traffic, seed = cell.conf, cell.traffic, cell.seed
    k, b, s = traffic["steps_per_dispatch"], traffic["batch"], traffic["seq"]
    compiled, params, opt, key, batches, first = build(conf, traffic, seed)
    params, opt, key, readout = first_epoch(compiled, params, opt, key, first, conf, seed)
    state = {"params": params, "opt": opt, "key": key, "steps": 0}

    def counters():
        jax.block_until_ready(state["params"])
        return {"steps": state["steps"]}

    traced = profile_window(cell, counters, TRACE_AT, TRACE_S)
    jax.block_until_ready(state["params"])
    setup_s = time.perf_counter() - cell.t_process
    from repro.analysis.guards import no_recompile

    with no_recompile():
        t0 = t_done = time.perf_counter()
        done, inflight = 0, None
        while t_done - t0 < cell.seconds:
            traced.poll(time.perf_counter() - t0)
            tokens = jnp.asarray(next(batches))
            with annotate("train.dispatch"):
                state["params"], state["opt"], state["key"], m = compiled(
                    state["params"], state["opt"], {"tokens": tokens}, state["key"])
            state["steps"] += k
            if inflight is not None:
                with annotate("train.wait"):
                    inflight.block_until_ready()
                done += k
                t_done = time.perf_counter()
            inflight = m["loss"]
        traced.close()
        inflight.block_until_ready()
    traced.load()

    rec = Record(cell=cell, setup_s=setup_s, attempted=k, failed=0)
    rec.e2e["train_tokens_per_s"] = done * b * s / (t_done - t0)
    rec.counters.update(traced.counters)
    rec.counters["steps_per_dispatch"] = k
    rec.trace, rec.window = traced.trace, traced.window
    rec.memory_peak_bytes = cell.memory_peak()
    del state, params, opt, compiled
    gc.collect()
    ref = reference.train_steps(conf, seed, first, train_key(seed), traffic["optimizer"], k)
    rec.check.update(compare(readout, ref))
    return rec
