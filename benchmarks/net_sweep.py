"""Channel x protocol x loss-rate scenario sweep (repro.net).

For every cell of the grid the sweep reports, against ONE trained COMtune
model:

* analytic per-round link latency (mean + p99) from the protocol policy's
  latency PMF (``repro.net.protocol``, generalizing paper Eq. 4-5),
* Monte-Carlo delivered fraction from stateful protocol rounds over the
  *bursty* channel (state carried across the test set), and
* DI accuracy with those exact per-sample delivery masks applied at the
  split (``repro.net.evalhook``).

Reduced-size by default — the full grid runs end-to-end on CPU in a couple
of minutes.  Results go to benchmarks/results/net_sweep.json.

    PYTHONPATH=src python -m benchmarks.net_sweep [--full] [--loss-rates ...]
"""

from __future__ import annotations

import argparse
import json
import os
import time
import zlib

import numpy as np

from repro.core.link import ChannelConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.net import (
    FECSpec,
    ARQProtocol,
    HybridFECARQProtocol,
    UnreliableProtocol,
    accuracy_with_packet_masks,
    make_channel,
    train_tiny_model,
)
from repro.net.evalhook import split_activations
from repro.net.protocol import latency_quantile

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

ELEMENTS_PER_PACKET = 25   # 100 B packets / 4 B floats


def build_channels(loss_rate: float):
    """The >=3-channel axis, all parameterized to comparable loss."""
    return {
        "iid": make_channel("iid", loss_rate),
        "ge": make_channel("ge", loss_rate),  # burst_len=4 Gilbert
        "fading": _fading_at(loss_rate),
    }


def _fading_at(loss_rate: float):
    """Pick a distance whose stationary fading loss is close to the target
    (bisection on the monotone distance -> loss curve)."""
    lo, hi = 5.0, 400.0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        ch = make_channel("fading", distance_m=mid)
        if ch.stationary_loss_rate < loss_rate:
            lo = mid
        else:
            hi = mid
    return make_channel("fading", distance_m=0.5 * (lo + hi))


def build_protocols():
    """The >=2-protocol axis."""
    return {
        "unreliable": UnreliableProtocol(),
        "arq": ARQProtocol(max_rounds=3),
        "fec_arq": HybridFECARQProtocol(fec=FECSpec(k=4, m=2), max_rounds=2),
    }


def sweep(loss_rates, n_eval: int, train_steps: int):
    model = train_tiny_model(steps=train_steps)
    acts = split_activations(model)
    n_elem = acts.shape[1]
    n_packets = -(-n_elem // ELEMENTS_PER_PACKET)
    n_eval = min(n_eval, acts.shape[0])
    model_eval = model
    if n_eval < acts.shape[0]:
        import dataclasses as _dc

        model_eval = _dc.replace(
            model, x_test=model.x_test[:n_eval], y_test=model.y_test[:n_eval]
        )
        acts = acts[:n_eval]

    rows = []
    for p in loss_rates:
        channels = build_channels(p)
        cfg = ChannelConfig(loss_rate=p)
        for ch_name, ch in channels.items():
            for pr_name, proto in build_protocols().items():
                t0 = time.time()
                lat, pmf = proto.latency_pmf(
                    n_packets, cfg, loss_rate=ch.stationary_loss_rate
                )
                mean_lat = float(np.dot(lat, pmf))
                p99_lat = latency_quantile(lat, pmf, 0.99)
                # Stateful MC rounds: one per eval sample, burst state
                # carried across the test set like consecutive requests.
                rng = np.random.RandomState(
                    zlib.crc32(f"{p}/{ch_name}/{pr_name}".encode()) % 2**31
                )
                state = ch.init_state(rng)
                masks = np.zeros((n_eval, n_packets), dtype=bool)
                slots = []
                for i in range(n_eval):
                    res, state = proto.run_round(rng, ch, state, n_packets)
                    masks[i] = res.delivered
                    slots.append(res.slots)
                acc = accuracy_with_packet_masks(
                    model_eval, masks, ELEMENTS_PER_PACKET, activations=acts
                )
                row = {
                    "loss_rate": p,
                    "channel": ch_name,
                    "protocol": pr_name,
                    "stationary_loss": ch.stationary_loss_rate,
                    "latency_mean_ms": mean_lat * 1e3,
                    "latency_p99_ms": p99_lat * 1e3,
                    "mc_slots_mean": float(np.mean(slots)),
                    "delivered_fraction": float(masks.mean()),
                    "accuracy": acc,
                    "wall_s": time.time() - t0,
                }
                rows.append(row)
                print(
                    f"p={p:.2f} {ch_name:>7s} x {pr_name:<10s} "
                    f"lat={row['latency_mean_ms']:7.3f}ms "
                    f"p99={row['latency_p99_ms']:7.3f}ms "
                    f"frac={row['delivered_fraction']:.3f} "
                    f"acc={acc:.3f}"
                )
    return rows


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--loss-rates", type=float, nargs="+",
                    default=[0.1, 0.3, 0.6])
    ap.add_argument("--full", action="store_true",
                    help="more eval samples + longer training")
    args = ap.parse_args()

    n_eval = 400 if args.full else 160
    train_steps = 300 if args.full else 120

    t0 = time.time()
    rows = sweep(args.loss_rates, n_eval=n_eval, train_steps=train_steps)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out = os.path.join(RESULTS_DIR, "net_sweep.json")
    with open(out, "w") as f:
        json.dump({"rows": rows, "wall_s": time.time() - t0}, f, indent=2,
                  default=float)
    print(f"\n{len(rows)} grid cells in {time.time() - t0:.1f}s -> {out}")


if __name__ == "__main__":
    main()
