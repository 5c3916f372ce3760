"""Channel-aware COMtune robustness benchmark (the paper's Fig. 6
generalized to bursty / FEC-protected links) + scan-compiled trainer
throughput, emitted as ``BENCH_comtune.json``.

Part A — robustness sweep: fine-tune the split CNN once per *training*
link emulation (``core.comtune.emulate_link``):

* ``dropout``     — the paper's Eq. 7 i.i.d. inverted dropout;
* ``channel_ge``  — the deployment channel: Gilbert–Elliott bursts with a
  ``shuffle=False`` sender (no anti-burst interleaving);
* ``channel_ge_fec`` (full mode) — same, FEC-protected, so training sees
  the *residual* post-decode loss pattern;

then evaluate every model on every *serving* channel (iid / GE bursts /
GE+FEC) at each loss rate.  The paper's claim, taken seriously: training
against the channel you deploy on (not its i.i.d. approximation) wins on
matched-channel accuracy — ``--assert-channel-wins`` enforces it.

Part B — trainer throughput: steps/s of the scan-compiled epoch
(``launch.steps.make_train_epoch``; K steps per dispatch) vs the per-step
jit loop on a dispatch-bound reduced LM config, both async-dispatch and
the seed driver's per-step ``float(loss)`` host-sync loop.

    PYTHONPATH=src python -m benchmarks.comtune_robustness \
        [--smoke] [--out BENCH_comtune.json] \
        [--assert-finite] [--assert-min-speedup 1.0] [--assert-channel-wins]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.data as data
from repro import obs
from repro.core import comtune
from repro.launch.compile_cache import setup_compile_cache
from repro.models import cnn
from repro.optim import AdamConfig, adam_update, init_adam

logger = obs.get_logger("comtune_robustness")

CNN_CFG = cnn.CNNConfig(
    blocks=((1, 16), (1, 32)), fc=(32,), num_classes=10,
    image_size=16, split_block=1,
)
BURST_LEN = 8.0                     # mean GE bad-sojourn, packets


# ---------------------------------------------------------------------------
# Part A: train-channel x eval-channel accuracy sweep
# ---------------------------------------------------------------------------

def train_specs(loss_rate: float, smoke: bool):
    """Training-link emulations, all routed through emulate_link."""
    ge = dict(
        train_link="channel", channel="ge", shuffle=False,
        loss_rate=loss_rate, channel_params=(("burst_len", BURST_LEN),),
    )
    out = {
        "dropout": comtune.LinkSpec(dropout_rate=loss_rate),
        "channel_ge": comtune.LinkSpec(**ge),
    }
    if not smoke:
        out["channel_ge_fec"] = comtune.LinkSpec(**ge, fec_k=10, fec_m=2)
    return out


def eval_specs(loss_rate: float, smoke: bool):
    """Serving channels (Eq. 12 path of emulate_link)."""
    out = {
        "iid": comtune.LinkSpec(loss_rate=loss_rate),
        "ge": comtune.LinkSpec(
            loss_rate=loss_rate, channel="ge", shuffle=False,
            channel_params=(("burst_len", BURST_LEN),),
        ),
    }
    if not smoke:
        out["ge_fec"] = comtune.LinkSpec(
            loss_rate=loss_rate, channel="ge", shuffle=False,
            channel_params=(("burst_len", BURST_LEN),), fec_k=10, fec_m=2,
        )
    return out


def finetune(dataset, spec, steps: int, seed: int = 0):
    (xtr, ytr), _ = dataset
    adam_cfg = AdamConfig(lr=2e-3)
    key = jax.random.PRNGKey(seed)
    params, state = cnn.init_cnn(key, CNN_CFG)
    opt = init_adam(params, adam_cfg)
    it = data.batch_iterator(xtr, ytr, 64, seed=seed)

    @jax.jit
    def step(params, state, opt, xb, yb, k):
        def loss_fn(p):
            link = lambda a: comtune.emulate_link(k, a, spec, "train")
            logits, new_state = cnn.forward(
                p, state, xb, CNN_CFG, train=True, link_fn=link
            )
            ll = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(ll, yb[:, None], axis=-1).mean(), new_state

        (l, new_state), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt, _ = adam_update(g, params, opt, adam_cfg)
        return params, new_state, opt, l

    for _ in range(steps):
        xb, yb = next(it)
        key, sub = jax.random.split(key)
        params, state, opt, _ = step(
            params, state, opt, jnp.asarray(xb), jnp.asarray(yb), sub
        )
    return params, state


def di_accuracy(dataset, model, spec, n_seeds: int) -> float:
    _, (xte, yte) = dataset
    params, state = model
    accs = []
    for s in range(n_seeds):
        key = jax.random.PRNGKey(1000 + s)
        link = lambda a: comtune.emulate_link(key, a, spec, "serve")
        logits, _ = cnn.forward(
            params, state, jnp.asarray(xte), CNN_CFG, train=False, link_fn=link
        )
        accs.append(float((jnp.argmax(logits, -1) == jnp.asarray(yte)).mean()))
    return float(np.mean(accs))


def robustness_sweep(smoke: bool) -> dict:
    loss_rates = [0.5] if smoke else [0.3, 0.5, 0.7]
    steps = 160 if smoke else 300
    n_seeds = 3 if smoke else 5
    dataset = data.make_image_dataset(
        n_train=1500, n_test=300 if smoke else 600, num_classes=10,
        image_size=16, noise=1.2,
    )
    matrix: dict = {}
    for p in loss_rates:
        models = {
            name: finetune(dataset, spec, steps)
            for name, spec in train_specs(p, smoke).items()
        }
        cell = {}
        for tname, model in models.items():
            cell[tname] = {"clean": di_accuracy(
                dataset, model, comtune.LinkSpec(), 1
            )}
            for ename, espec in eval_specs(p, smoke).items():
                cell[tname][ename] = di_accuracy(dataset, model, espec, n_seeds)
        matrix[str(p)] = cell
    return {
        "loss_rates": loss_rates,
        "train_steps": steps,
        "eval_seeds": n_seeds,
        "burst_len": BURST_LEN,
        "accuracy": matrix,
    }


# ---------------------------------------------------------------------------
# Part B: scan-compiled trainer vs per-step loop
# ---------------------------------------------------------------------------

def trainer_bench(smoke: bool, arch: str = "qwen1.5-0.5b") -> dict:
    from repro.configs import get_config
    from repro.launch.steps import make_train_epoch, make_train_step
    from repro.models import lm

    # Dispatch-bound reduced config: the regime the scan targets (same as
    # the PR-2 decode engine) — per-step XLA dispatch is a large fraction
    # of step wall time, so fusing K steps into one program pays.
    cfg = get_config(arch).reduced(
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
        vocab_size=64, num_units=1,
    )
    cfg = cfg.with_updates(num_layers=len(cfg.prologue) + len(cfg.unit_pattern))
    B, S, K = 2, 16, 100 if smoke else 200
    repeats = 3
    adam_cfg = AdamConfig(lr=3e-4, grad_clip_norm=1.0)
    toks = jax.random.randint(
        jax.random.PRNGKey(7), (K, B, S), 0, cfg.vocab_size, jnp.int32
    )

    def fresh():
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        return params, init_adam(params, adam_cfg)

    step_fn = jax.jit(make_train_step(cfg, adam_cfg))
    p, o = fresh()
    _, sub = jax.random.split(jax.random.PRNGKey(42))
    p, o, m = step_fn(p, o, {"tokens": toks[0]}, sub)
    jax.block_until_ready(m["loss"])

    def run_loop(sync_every_step: bool):
        nonlocal p, o
        key = jax.random.PRNGKey(42)
        t0 = time.perf_counter()
        for i in range(K):
            key, sub = jax.random.split(key)
            p, o, m = step_fn(p, o, {"tokens": toks[i]}, sub)
            if sync_every_step:
                float(m["loss"])      # the seed driver's per-step host sync
        jax.block_until_ready((p, o))
        return time.perf_counter() - t0

    t_loop = min(run_loop(False) for _ in range(repeats))
    t_loop_synced = min(run_loop(True) for _ in range(repeats))

    epoch_fn = make_train_epoch(cfg, adam_cfg)
    p2, o2 = fresh()
    t0 = time.perf_counter()
    r = epoch_fn(p2, o2, {"tokens": toks}, jax.random.PRNGKey(42))
    jax.block_until_ready(r[0])
    compile_s = time.perf_counter() - t0
    p2, o2 = r[0], r[1]

    def run_scan():
        nonlocal p2, o2
        t0 = time.perf_counter()
        r = epoch_fn(p2, o2, {"tokens": toks}, jax.random.PRNGKey(43))
        jax.block_until_ready((r[0], r[3]["loss"]))
        p2, o2 = r[0], r[1]
        return time.perf_counter() - t0

    t_scan = min(run_scan() for _ in range(repeats))
    return {
        "arch": cfg.name,
        "batch": B,
        "seq": S,
        "steps_per_epoch": K,
        "loop_steps_per_s": K / t_loop,
        "loop_synced_steps_per_s": K / t_loop_synced,
        "scan_steps_per_s": K / t_scan,
        "scan_compile_s": compile_s,
        "speedup_scan_vs_loop": t_loop / t_scan,
        "speedup_scan_vs_synced_loop": t_loop_synced / t_scan,
    }


# ---------------------------------------------------------------------------


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_comtune.json")
    ap.add_argument(
        "--assert-finite", action="store_true",
        help="fail if any sweep accuracy is non-finite",
    )
    ap.add_argument(
        "--assert-min-speedup", type=float, default=None,
        help="fail if scan/loop trainer speedup is below this",
    )
    ap.add_argument(
        "--assert-channel-wins", action="store_true",
        help="fail unless channel_ge-tuned beats dropout-tuned on the "
             "matched GE eval at every swept loss rate",
    )
    args = ap.parse_args()

    sweep = robustness_sweep(args.smoke)
    trainer = trainer_bench(args.smoke)
    result = {
        "bench": "comtune_robustness",
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "sweep": sweep,
        "trainer": trainer,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)

    for p, cell in sweep["accuracy"].items():
        row = " | ".join(
            f"{t}: ge {a['ge']:.3f} iid {a['iid']:.3f}" for t, a in cell.items()
        )
        logger.info(f"p={p}: {row}")
    logger.info(
        f"trainer[{trainer['arch']} b={trainer['batch']} s={trainer['seq']} "
        f"K={trainer['steps_per_epoch']}]: "
        f"scan {trainer['scan_steps_per_s']:.0f} steps/s vs "
        f"loop {trainer['loop_steps_per_s']:.0f} "
        f"(synced {trainer['loop_synced_steps_per_s']:.0f}) -> "
        f"{trainer['speedup_scan_vs_loop']:.2f}x -> {args.out}"
    )

    ok = True
    accs = [
        v for cell in sweep["accuracy"].values()
        for a in cell.values() for v in a.values()
    ]
    if args.assert_finite and not np.all(np.isfinite(accs)):
        logger.error("ASSERT FAILED: non-finite accuracy in sweep")
        ok = False
    if args.assert_min_speedup is not None and (
        trainer["speedup_scan_vs_loop"] < args.assert_min_speedup
    ):
        logger.info(
            f"ASSERT FAILED: speedup {trainer['speedup_scan_vs_loop']:.2f} < "
            f"{args.assert_min_speedup}"
        )
        ok = False
    if args.assert_channel_wins:
        for p, cell in sweep["accuracy"].items():
            if cell["channel_ge"]["ge"] <= cell["dropout"]["ge"]:
                logger.info(
                    f"ASSERT FAILED: p={p} channel_ge {cell['channel_ge']['ge']:.3f}"
                    f" <= dropout {cell['dropout']['ge']:.3f} on matched GE eval"
                )
                ok = False
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
