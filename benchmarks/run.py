"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time of the
benchmark; derived = the figure's headline metric) and dumps all figure
data to benchmarks/results/paper_figs.json.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig5,...]
"""

from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks import paper_figs, roofline_report

from repro.launch.compile_cache import setup_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

BENCHES = [
    ("fig4a_latency_cdf", paper_figs.fig4a_latency_cdf),
    ("fig4b_accuracy_cdf", paper_figs.fig4b_accuracy_cdf),
    ("fig5_loss_robustness", paper_figs.fig5_loss_robustness),
    ("fig6_compression", paper_figs.fig6_compression),
    ("fig7_compression_loss", paper_figs.fig7_compression_loss),
    ("fig8_msgsize_loss", paper_figs.fig8_msgsize_loss),
    ("beyond_packet_granularity", paper_figs.beyond_packet_granularity),
]


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    os.makedirs(RESULTS_DIR, exist_ok=True)
    all_rows = {}
    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if only and name not in only:
            continue
        t0 = time.time()
        rows, derived = fn()
        dt_us = (time.time() - t0) * 1e6
        all_rows[name] = {"rows": rows, "derived": derived}
        print(f"{name},{dt_us:.0f},{derived:.4f}")

    if not args.skip_roofline:
        t0 = time.time()
        summary = roofline_report.run()
        dt_us = (time.time() - t0) * 1e6
        all_rows["roofline"] = summary
        print(
            f"roofline_report,{dt_us:.0f},"
            f"{summary['single_pod_pairs'] + summary['multi_pod_pairs']}"
        )

    with open(os.path.join(RESULTS_DIR, "paper_figs.json"), "w") as f:
        json.dump(all_rows, f, indent=2, default=float)


if __name__ == "__main__":
    main()
