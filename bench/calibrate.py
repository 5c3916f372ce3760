#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip:

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process (compiled programs shared across seeds; a
serving seed gets an engine of its own): the program's numbers, as a run
compares them (the lower readings; where the cell's limits give
``served_gap.router_tie``, with router near-ties resolved, and the plain
gap and the flips taken beside them); the control's — the reference in
float8 (e4m3 operands, e5m2 cotangents) put in the program's place — (the
upper readings); and the planted faults of the cell's kind:

* serving: a served token altered where it is produced (the longest
  sampled request's middle token);
* training: half of the batch left out, the mean over the rest; and a
  step that returns its state unchanged, which reads 1 on the change by
  the measure itself and needs no run.

One JSON line per seed.  ``bench/limits/<cell>.json`` records the limit
chosen from them and the readings it was set from.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness, loadgen, model, reference, serve, train  # noqa: E402
from bench.harness import _Untraced  # noqa: E402


def serve_seed(conf, traffic, seed, seconds, gap=None):
    """One seed's readings, on an engine of its own, built and warmed for
    this seed and freed before the reference runs (as ``serve.run`` does):
    no request outlives its seed, and the reference has the device to
    itself.  ``gap`` is the cell's ``served_gap`` limits entry."""
    params = model.program_params(conf, seed)
    engine = serve.engine_for(model.program_config(conf, traffic["link"]), traffic["pool"])
    reqs = loadgen.schedule(traffic, seed, seconds, conf["vocab_size"])
    keys = serve._request_keys(seed, len(reqs))
    serve.warm(engine, params, reqs)
    win = serve.Window(engine, params, reqs, traffic, keys)
    t0, end = win.run(seconds, _Untraced())
    due = [r for r in reqs if t0 <= r.due_at < end]
    done = [r for r in due if r.finished == r.finished]
    sample = serve.pick_sample(done, seed, traffic["check"])
    del engine, params, win
    gc.collect()
    out = serve.check_sample(conf, traffic, seed, sample, keys, control=True, gap=gap)
    longest = sample[0]
    mid = len(longest.tokens) // 2
    longest.tokens = np.array(longest.tokens)
    longest.tokens[mid] = (longest.tokens[mid] + 1) % conf["vocab_size"]
    altered = serve.check_sample(conf, traffic, seed, sample, keys, gap=gap)["served"]
    row = {"seed": seed, "attempted": len(due), "failed": len(due) - len(done),
           "sampled_tokens": sum(len(r.tokens) for r in sample),
           "served_gap": out["served"], "control_gap": out["control"],
           "altered_token_gap": altered}
    if "router_flips" in out:
        row.update(served_gap_plain=out["served_plain"], router_flips=out["router_flips"],
                   control_gap_plain=out["control_plain"], control_flips=out["control_flips"])
    return row


def train_seed(compiled, conf, traffic, seed):
    from repro.optim import init_adam

    k, b, s = traffic["steps_per_dispatch"], traffic["batch"], traffic["seq"]
    params = model.program_params(conf, seed)
    opt = init_adam(params, train.adam_config(traffic["optimizer"]))
    first = next(train.feed(seed, conf["vocab_size"], k, b, s))
    key = train.train_key(seed)
    params, opt, _, prog = train.first_epoch(compiled, params, opt, key, first, conf, seed)
    del params, opt
    gc.collect()
    opt_conf = traffic["optimizer"]
    ref = reference.train_steps(conf, seed, first, key, opt_conf, k)
    ctl = reference.train_steps(conf, seed, first, key, opt_conf, k, prec="fp8")
    half = reference.train_steps(conf, seed, first, key, opt_conf, k, half_batch=True)
    row = {"seed": seed, "loss_ref": ref["loss"], "loss_program": prog["loss"]}
    for name, got in (("program", prog), ("control", ctl), ("half_batch", half)):
        row.update({f"{name}.{m}": v for m, v in train.compare(got, ref).items()})
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    _, _, conf, traffic, limits = harness.find_cell(ROOT, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if traffic["kind"] == "serve":
        for seed in seeds:
            t = time.perf_counter()
            row = serve_seed(conf, traffic, seed, args.seconds, limits.get("served_gap"))
            print(json.dumps(dict(row, seconds=time.perf_counter() - t)), flush=True)
    else:
        compiled, params, opt, *_ = train.build(conf, traffic, seeds[0])
        del params, opt
        for seed in seeds:
            t = time.perf_counter()
            row = train_seed(compiled, conf, traffic, seed)
            print(json.dumps(dict(row, seconds=time.perf_counter() - t)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
