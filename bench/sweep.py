#!/usr/bin/env python3
"""Find the highest open-loop rate a serving cell sustains, on the chip:

    python bench/sweep.py --workload qwen05b-serve-iot --rates 10,15,20 \\
        --seconds 20 --seed 1

For each offered rate (requests/s) the cell's mix runs for ``--seconds``
through one warm engine, and the line printed says what came out: the
completed rate, tokens/s, latency quantiles in each half of the window,
and the backlog at the close (requests due in the window that had not
finished by its end).  A rate is sustained where the backlog stays
near zero and the second half's median latency is not above the first's
by more than half.  The cell's rate is then set to about four fifths of
the highest sustained one, by hand, in its traffic file.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness, loadgen, model, serve  # noqa: E402
from bench.harness import _Untraced  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    _, _, conf, traffic, _ = harness.find_cell(ROOT, args.workload)
    cfg = model.program_config(conf, traffic["link"])
    params = model.program_params(conf, args.seed)
    engine = serve.engine_for(cfg, traffic["pool"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dict(traffic, arrivals=dict(traffic["arrivals"], rate_per_s=rate))
        reqs = loadgen.schedule(t, args.seed + i, args.seconds, conf["vocab_size"])
        keys = serve._request_keys(args.seed + i, len(reqs))
        if i == 0:
            serve.warm(engine, params, reqs)
        win = serve.Window(engine, params, reqs, t, keys)
        t0, end = win.run(args.seconds, _Untraced())
        due = [r for r in reqs if t0 <= r.due_at < end]
        done = [r for r in due if r.finished == r.finished]
        lat = np.array([r.finished - r.due_at for r in done])
        half = [r.finished - r.due_at for r in done if r.due_at < t0 + args.seconds / 2]
        late = [r.finished - r.due_at for r in done if r.due_at >= t0 + args.seconds / 2]
        in_win = [r for r in due if r.finished <= end]
        print(json.dumps({
            "rate": rate, "due": len(due), "completed_in_window_per_s": len(in_win) / args.seconds,
            "tokens_per_s": sum(r.n_out for r in in_win) / args.seconds,
            "p50_ms_first_half": float(np.median(half) * 1e3) if half else None,
            "p50_ms_second_half": float(np.median(late) * 1e3) if late else None,
            "p95_ms": float(np.percentile(lat, 95) * 1e3) if len(lat) else None,
            "backlog_at_close": sum(1 for r in due if not r.finished <= end),
            "engine_steps": engine.steps,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
