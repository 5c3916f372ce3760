"""Real training driver (CPU-scale): COMtune fine-tuning of a reduced
architecture on the synthetic LM stream — channel-aware, scan-compiled,
optionally data-parallel sharded, with periodic checkpointing + resume.

The trainer got the PR-2 serving treatment: by default it runs K steps per
dispatch as ONE jitted ``lax.scan`` epoch (``launch.steps.make_train_epoch``
— donated params/opt-state, per-step key-split chain identical to the
Python loop, so loss trajectories are bit-identical to ``--no-epoch-scan``)
and can shard params/opt-state/batches over the host mesh
(``--sharded``, ``launch.steps.build_sharded_epoch``).

The emulated link at the split point is a full ``core.comtune.LinkSpec``:
``--train-link channel`` fine-tunes against the *deployment* channel
(``--train-channel ge`` bursts, ``--no-shuffle`` senders, ``--train-fec
10,2`` residual-loss patterns) instead of the paper's i.i.d. dropout, and
``--curriculum p0:p1`` ramps the emulation rate across the run.  For the
dropout / plain-iid emulations the ramp is applied PER STEP as traced scan
data (one compiled epoch program per epoch shape); the stateful channels
fall back to scan-epoch granularity, each chunk compiling its static rate.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 200 --batch 8 --seq 128 [--full-size] \
        [--link off|train] [--train-link dropout|channel] \
        [--train-channel ge] [--train-fec 10,2] [--no-shuffle] \
        [--curriculum 0.1:0.4] [--sharded] [--no-epoch-scan] \
        [--ckpt-dir DIR --ckpt-every 100] [--resume]
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import ARCHITECTURES, get_config
from repro.configs.base import ShapeConfig
from repro.data import lm_batch_iterator, make_lm_dataset
from repro import obs
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (
    build_sharded_epoch,
    build_sharded_step,
    make_train_epoch,
    make_train_step,
)
from repro.models import lm
from repro.optim import AdamConfig, init_adam, schedule

logger = obs.get_logger("train")

# Per-step link stats the train step/epoch metrics now carry (launch.steps).
_LINK_KEYS = ("link_elems", "link_dropped", "fec_recovered_packets")


def build_train_link_spec(
    cfg,
    train_link: Optional[str] = None,
    train_channel: Optional[str] = None,
    train_fec: Optional[Tuple[int, int]] = None,
    shuffle: Optional[bool] = None,
    loss_rate: Optional[float] = None,
):
    """The trainer's ``LinkSpec``: cfg.link plus the channel-aware CLI
    overrides.  ``train_fec`` is (k, m); ``loss_rate`` seeds the channel
    rate the "channel" emulation trains against.  Asking for a train
    channel or train FEC implies ``train_link="channel"`` — those knobs
    are dead under the dropout emulation."""
    spec = lm.link_spec_from_config(cfg)
    updates = {}
    if train_link is None and (train_channel is not None or train_fec is not None):
        train_link = "channel"
    if train_link is not None:
        updates["train_link"] = train_link
    if train_channel is not None:
        updates["channel"] = train_channel
    if train_fec is not None:
        updates["fec_k"], updates["fec_m"] = train_fec
    if shuffle is not None:
        updates["shuffle"] = shuffle
    spec = dataclasses.replace(spec, **updates)
    if loss_rate is not None:
        spec = spec.with_channel_loss_rate(loss_rate)
    return spec


def per_step_curriculum_ok(spec) -> bool:
    """True when the ramped rate can be fed as TRACED per-step scan data
    (one compiled epoch program for the whole ramp): the dropout emulation
    and the plain-iid channel draw their masks directly from the rate.
    The stateful channels (GE/fading/trace) and FEC bake the rate into
    static tables, so they keep the chunked epoch-static ramp."""
    if spec.train_link == "dropout":
        return True
    return spec.channel in ("", "iid") and spec.fec_m <= 0


def curriculum_rates(steps: int, curriculum: Tuple[float, float]) -> np.ndarray:
    """The per-step linear ramp p0 -> p1 over the whole run (float32)."""
    p0, p1 = curriculum
    if steps <= 1:
        return np.full((max(steps, 1),), p0, np.float32)
    return np.linspace(p0, p1, steps, dtype=np.float32)


def curriculum_schedule(
    steps: int, steps_per_epoch: int, curriculum: Optional[Tuple[float, float]]
):
    """Split the run into scan-epoch chunks of (start_step, n_steps, rate).

    ``rate`` is None without a curriculum (the spec's own rate applies);
    with ``curriculum=(p0, p1)`` it ramps linearly over the chunks.  The
    rate is static per chunk — each distinct rate compiles its own epoch
    program (compile-cached, so revisited rates never re-trace).  The
    iid/dropout train paths instead ramp per STEP with traced rates
    (``per_step_curriculum_ok``): the chunk rate is ignored and a
    ``link_rate`` slice of :func:`curriculum_rates` rides the batch dict,
    keeping the compile count at 1 per epoch shape.
    """
    chunks = []
    start = 0
    while start < steps:
        chunks.append((start, min(steps_per_epoch, steps - start)))
        start += steps_per_epoch
    if curriculum is None:
        return [(s, n, None) for s, n in chunks]
    p0, p1 = curriculum
    denom = max(len(chunks) - 1, 1)
    return [
        (s, n, p0 + (p1 - p0) * i / denom) for i, (s, n) in enumerate(chunks)
    ]


def train(
    arch: str,
    steps: int = 200,
    batch: int = 8,
    seq: int = 128,
    lr: float = 3e-4,
    link_mode: str = "train",
    full_size: bool = False,
    ckpt_dir: str | None = None,
    log_every: int = 20,
    seed: int = 0,
    *,
    train_link: Optional[str] = None,
    train_channel: Optional[str] = None,
    train_fec: Optional[Tuple[int, int]] = None,
    shuffle: Optional[bool] = None,
    train_loss_rate: Optional[float] = None,
    curriculum: Optional[Tuple[float, float]] = None,
    epoch_scan: bool = True,
    steps_per_epoch: int = 0,
    sharded: bool = False,
    fsdp: str = "off",
    ckpt_every: int = 0,
    resume: bool = False,
    profile_dir: Optional[str] = None,
):
    """Returns (params, losses, cfg); ``losses`` covers the steps run by
    THIS call (so a resumed run returns the tail of the trajectory)."""
    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    adam_cfg = AdamConfig(
        lr=lr,
        grad_clip_norm=1.0,
        schedule=schedule.warmup_cosine(max(10, steps // 20), steps),
    )
    key = jax.random.PRNGKey(seed)
    params = lm.init_lm(key, cfg)
    opt_state = init_adam(params, adam_cfg)
    link_spec = build_train_link_spec(
        cfg, train_link=train_link, train_channel=train_channel,
        train_fec=train_fec, shuffle=shuffle, loss_rate=train_loss_rate,
    )
    # Per-step traced curriculum: the iid/dropout emulations take the
    # ramped rate as scan DATA (batches["link_rate"]), so the whole ramp
    # runs in one compiled epoch program per epoch shape.  The stateful
    # channels keep the chunked epoch-static ramp (their rates are baked
    # into static transition tables at trace time).
    per_step = (
        curriculum is not None
        and epoch_scan
        and not sharded
        and per_step_curriculum_ok(link_spec)
    )
    if steps_per_epoch <= 0:
        steps_per_epoch = min(steps, 50)
        if curriculum is not None and not per_step:
            # An epoch-static ramp needs multiple chunks (each chunk's rate
            # is static); default to ~5 rather than pinning at p0.
            steps_per_epoch = min(steps_per_epoch, max(1, -(-steps // 5)))
    elif curriculum is not None and not per_step and steps_per_epoch >= steps > 1:
        logger.warning(
            "warning: --curriculum with a single epoch chunk "
            f"(--steps-per-epoch {steps_per_epoch} >= --steps {steps}) "
            "trains entirely at the start rate"
        )
    if link_spec.train_link == "channel" and (
        curriculum is not None or train_loss_rate is not None
    ):
        from repro.net.channels import supports_target_rate

        if not supports_target_rate(
            link_spec.channel or "iid", link_spec.channel_params
        ):
            logger.warning(
                f"warning: --curriculum/--train-loss-rate have no effect on "
                f"the {link_spec.channel!r} channel (its loss rate comes "
                f"from its own physics/trace, not loss_rate)"
            )
            # Don't compile one epoch program per (identical) ramped rate.
            curriculum = None
    elif train_loss_rate is not None and link_spec.train_link != "channel":
        logger.warning(
            "warning: --train-loss-rate only affects --train-link channel; "
            "the dropout emulation draws at the dropout rate "
            f"({link_spec.dropout_rate})"
        )

    start_step = 0
    if resume:
        assert ckpt_dir, "--resume needs --ckpt-dir"
        template = {"params": params, "opt_state": opt_state, "key": key}
        restored, start_step = restore_checkpoint(
            ckpt_dir, template, name="train"
        )
        params, opt_state = restored["params"], restored["opt_state"]
        key = restored["key"]
        logger.info(f"resumed from {ckpt_dir} at step {start_step}")

    tokens = make_lm_dataset(cfg.vocab_size, n_tokens=max(100_000, batch * seq * 50))
    it = lm_batch_iterator(tokens, batch, seq, seed=seed)
    for _ in range(start_step):      # replay the stream up to the resume point
        next(it)

    mesh = make_host_mesh() if sharded else None
    shape_cfg = ShapeConfig("train_cli", seq, batch, "train")
    fe = (
        jnp.zeros((batch, cfg.frontend_len, cfg.d_model), jnp.float32)
        if cfg.frontend
        else None
    )

    def spec_for(rate):
        return link_spec if rate is None else link_spec.with_train_rate(rate)

    # Compile caches keyed on the (static) curriculum rate so revisited
    # rates — and the no-curriculum case — trace exactly once.
    epoch_fns: dict = {}
    step_fns: dict = {}

    def get_epoch_fn(rate, n_steps):
        k = (rate, n_steps)
        if k not in epoch_fns:
            if sharded:
                sc = dataclasses.replace(shape_cfg, name=f"train_cli_{n_steps}")
                epoch_fns[k], _ = build_sharded_epoch(
                    cfg, sc, mesh, n_steps, adam_cfg=adam_cfg,
                    link_mode=link_mode, link_spec=spec_for(rate), fsdp=fsdp,
                )
            else:
                epoch_fns[k] = make_train_epoch(
                    cfg, adam_cfg, link_mode=link_mode, link_spec=spec_for(rate)
                )
        return epoch_fns[k]

    def get_step_fn(rate):
        if rate not in step_fns:
            if sharded:
                sc = dataclasses.replace(shape_cfg, name="train_cli_step")
                step_fns[rate], _ = build_sharded_step(
                    cfg, sc, mesh, adam_cfg=adam_cfg, link_mode=link_mode,
                    link_spec=spec_for(rate), fsdp=fsdp,
                )
            else:
                step_fns[rate] = jax.jit(make_train_step(
                    cfg, adam_cfg, link_mode=link_mode, link_spec=spec_for(rate)
                ))
        return step_fns[rate]

    losses: list = []        # device scalars / arrays; synced lazily
    t0 = time.time()
    done = 0                 # steps completed by this call

    def log(step_global):
        # One host sync per log point: block on the freshest state, then
        # read the buffered device losses (satellite fix: the old driver
        # called float(loss) EVERY step, forcing a per-step host sync that
        # defeated async dispatch).
        jax.block_until_ready((params, opt_state))
        last = float(np.asarray(losses[-1]).reshape(-1)[-1])
        logger.info(
            f"step {step_global:5d} loss {last:.4f} "
            f"({(time.time()-t0)/max(done, 1):.2f}s/step)"
        )

    def maybe_ckpt(step_global, grid=1):
        # ``grid`` is the stride maybe_ckpt is called at (the chunk size in
        # the scan-epoch path): save whenever a ckpt_every point fell
        # within the last ``grid`` steps, same test as log()'s log points.
        if ckpt_dir and ckpt_every and (
            step_global % ckpt_every < grid or step_global == steps
        ):
            save_checkpoint(
                ckpt_dir, step_global,
                {"params": params, "opt_state": opt_state, "key": key},
                name="train",
            )

    rates_global = (
        curriculum_rates(steps, curriculum) if per_step else None
    )
    chunks = curriculum_schedule(steps, steps_per_epoch, curriculum)
    # Observability: the registry span / profiler wrap dispatch only (no
    # extra host syncs); link-stat device scalars are buffered like the
    # losses and summed once after the loop.
    reg = obs.registry()
    link_dev: list = []
    _obs_ctx = contextlib.ExitStack()
    _obs_ctx.enter_context(obs.exporters.jax_profile(profile_dir))
    _obs_ctx.enter_context(
        reg.span("train.run", arch=arch, steps=steps, sharded=sharded)
    )
    try:
      for chunk_start, n_steps, rate in chunks:
          if chunk_start + n_steps <= start_step:
              continue  # fully covered by the restored checkpoint
          if epoch_scan and chunk_start >= start_step:
              stack = np.stack([next(it) for _ in range(n_steps)])
              batches = {"tokens": jnp.asarray(stack)}
              if fe is not None:
                  batches["frontend_embed"] = jnp.broadcast_to(
                      fe, (n_steps,) + fe.shape
                  )
              if per_step:
                  # Traced per-step ramp: the rate is scan data, the epoch
                  # program is shared across every chunk of this shape.
                  batches["link_rate"] = jnp.asarray(
                      rates_global[chunk_start : chunk_start + n_steps]
                  )
                  rate = None
              epoch_fn = get_epoch_fn(rate, n_steps)
              with reg.span("train.epoch", start=chunk_start, steps=n_steps):
                  params, opt_state, key, metrics = epoch_fn(
                      params, opt_state, batches, key
                  )
              losses.append(metrics["loss"])
              link_dev.append({k: metrics[k] for k in _LINK_KEYS})
              done += n_steps
              step_global = chunk_start + n_steps
              if step_global % log_every < n_steps or step_global == steps:
                  log(step_global)
              maybe_ckpt(step_global, grid=n_steps)
          else:
              # Per-step path: the scan oracle/baseline, and how a resume
              # that lands mid-chunk re-aligns to the chunk grid.
              step_fn = get_step_fn(None if per_step else rate)
              for i in range(n_steps):
                  step_global = chunk_start + i + 1
                  if step_global <= start_step:
                      continue
                  b = {"tokens": jnp.asarray(next(it))}
                  if fe is not None:
                      b["frontend_embed"] = fe
                  if per_step:
                      b["link_rate"] = jnp.asarray(rates_global[step_global - 1])
                  key, sub = jax.random.split(key)
                  params, opt_state, metrics = step_fn(params, opt_state, b, sub)
                  losses.append(metrics["loss"])
                  link_dev.append({k: metrics[k] for k in _LINK_KEYS})
                  done += 1
                  if step_global % log_every == 0 or step_global == steps:
                      log(step_global)
                  maybe_ckpt(step_global)

    finally:
        _obs_ctx.close()

    if reg.enabled and link_dev:
        tot = {
            k: float(sum(float(np.asarray(d[k], np.float64).sum())
                         for d in link_dev))
            for k in _LINK_KEYS
        }
        for k, v in tot.items():
            reg.counter(f"train.{k}").inc(v)
        reg.gauge("train.realized_drop_rate").set(
            tot["link_dropped"] / max(tot["link_elems"], 1.0)
        )

    if ckpt_dir and not ckpt_every:
        save_checkpoint(
            ckpt_dir, steps,
            {"params": params, "opt_state": opt_state, "key": key},
            name="train",
        )
        logger.info(f"saved checkpoint to {ckpt_dir}")
    flat = np.concatenate([np.asarray(l).reshape(-1) for l in losses]) \
        if losses else np.zeros(0)
    return params, list(map(float, flat)), cfg


def _parse_curriculum(s: Optional[str]):
    if not s:
        return None
    p0, p1 = s.split(":")
    return float(p0), float(p1)


def _parse_fec(s: Optional[str]):
    if not s:
        return None
    k, m = s.split(",")
    return int(k), int(m)


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHITECTURES), required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--link", default="train", choices=["train", "off"])
    ap.add_argument(
        "--train-link", default=None, choices=["dropout", "channel"],
        help="what emulates the channel in Eq. 8 (default: cfg.link)",
    )
    ap.add_argument(
        "--train-channel", default=None,
        choices=["iid", "ge", "gilbert_elliott", "fading"],
        help="channel process for --train-link channel",
    )
    ap.add_argument(
        "--train-fec", default=None, metavar="K,M",
        help="packet FEC on the emulated train link, e.g. 10,2",
    )
    ap.add_argument(
        "--train-loss-rate", type=float, default=None,
        help="channel loss rate the 'channel' emulation trains against",
    )
    ap.add_argument(
        "--no-shuffle", action="store_true",
        help="emulate a sender without the paper's anti-burst interleaving",
    )
    ap.add_argument(
        "--curriculum", default=None, metavar="P0:P1",
        help="ramp the train-link rate from P0 to P1 across the run",
    )
    ap.add_argument(
        "--no-epoch-scan", action="store_true",
        help="per-step jit loop instead of the scan-compiled epoch",
    )
    ap.add_argument("--steps-per-epoch", type=int, default=0)
    ap.add_argument(
        "--sharded", action="store_true",
        help="data-parallel over the host mesh (batch-sharded inputs)",
    )
    ap.add_argument(
        "--fsdp", default="off", choices=["on", "off", "expert"],
        help="parameter/opt-state sharding rules for --sharded "
             "(off = replicated; see sharding.rules.param_pspecs)",
    )
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument(
        "--ckpt-every", type=int, default=0,
        help="save params/opt-state/key every N steps (with the scan-epoch "
             "executor, at the epoch boundaries that land on the N grid)",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="restore the latest checkpoint in --ckpt-dir and continue",
    )
    ap.add_argument(
        "--profile-dir", default=None,
        help="wrap the run in jax.profiler.trace writing to this directory "
             "(view with TensorBoard or ui.perfetto.dev)",
    )
    args = ap.parse_args()
    _, losses, _ = train(
        args.arch,
        steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        lr=args.lr,
        link_mode=args.link,
        full_size=args.full_size,
        ckpt_dir=args.ckpt_dir,
        train_link=args.train_link,
        train_channel=args.train_channel,
        train_fec=_parse_fec(args.train_fec),
        train_loss_rate=args.train_loss_rate,
        shuffle=False if args.no_shuffle else None,
        curriculum=_parse_curriculum(args.curriculum),
        epoch_scan=not args.no_epoch_scan,
        steps_per_epoch=args.steps_per_epoch,
        sharded=args.sharded,
        fsdp=args.fsdp,
        ckpt_every=args.ckpt_every,
        resume=args.resume,
        profile_dir=args.profile_dir,
    )
    if losses:
        logger.info(
            f"final loss {np.mean(losses[-10:]):.4f} "
            f"(start {np.mean(losses[:5]):.4f})"
        )


if __name__ == "__main__":
    main()
