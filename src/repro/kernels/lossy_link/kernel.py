"""Fused lossy-link egress kernel (the paper's split-point hot path).

One pass over the split activation performs, per element:

    quantize (clip -> n-bit code)  ->  packet-loss mask  ->  dequantize
    ->  1/(1-p) compensation                                   (Eq. 13-15 + 10-11)

On the serving path this is executed once per DI round on the device side;
fusing it avoids three HBM round-trips of the (tokens, d_model) activation.
Uniform random draws are precomputed outside (jax.random) and streamed in —
on a real TPU deployment these could come from pltpu.prng_random_bits, but
keeping RNG outside makes interpret-mode validation bit-exact against the
jnp oracle.

Tiling: (block_t, block_d) VMEM tiles over the (tokens, d_model) activation;
the per-feature scale factors ride as (1, D) rows in (1, block_d) tiles
broadcast down the token axis (TPU has no 1-D VMEM block).  block_d is a
multiple of 128 (VPU lane width).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import pallas_interpret


def _egress_kernel(
    x_ref, u_ref, smin_ref, smax_ref, o_ref, *, bits: int, loss_rate: float
):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    s_min = smin_ref[...].astype(jnp.float32)                 # (1, block_d)
    s_max = smax_ref[...].astype(jnp.float32)

    levels = jnp.float32(2**bits - 1)
    rng = jnp.maximum(s_max - s_min, 1e-8)
    clipped = jnp.clip(x, s_min, s_max)
    code = jnp.round((clipped - s_min) / rng * levels)
    deq = code / levels * rng + s_min

    keep = u >= jnp.float32(loss_rate)
    comp = 1.0 / max(1.0 - float(loss_rate), 1e-6) if loss_rate > 0.0 else 1.0
    comp = jnp.float32(comp)
    y = jnp.where(keep, deq * comp, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Gilbert–Elliott burst-mask kernel (repro.net serving hot path)
# ---------------------------------------------------------------------------

def _burst_mask_kernel(
    uinit_ref, uloss_ref, utr_ref, o_ref,
    *, p_gb: float, p_bg: float, loss_good: float, loss_bad: float,
    n_valid: int,
):
    """One block of independent Gilbert–Elliott chains.

    Lanes are independent channel realizations (one per message in the
    serving batch); sublane rows are packets in sequence.  The hidden
    Good/Bad state is carried down the packet axis by a ``fori_loop``
    reading and writing one row per step — the chain is inherently
    sequential in time, but the whole block of chains advances in lockstep
    on the VPU, so the Markov process never leaves the device.  Packets sit
    on the sublane axis because TPU loads a dynamic row offset, but not a
    dynamic single-lane column.
    """
    pi_b = p_gb / max(p_gb + p_bg, 1e-12)
    # The state rides the loop as int32 0/1 and every bool is consumed by a
    # select: Mosaic can neither carry nor convert i1 vectors.
    one, zero = jnp.int32(1), jnp.int32(0)
    bad0 = jnp.where(uinit_ref[...] < jnp.float32(pi_b), one, zero)  # (1, block_r)

    def body(t, bad):
        bad = bad != 0
        ul = uloss_ref[pl.ds(t, 1), :]                         # (1, block_r)
        ut = utr_ref[pl.ds(t, 1), :]
        p = jnp.where(bad, jnp.float32(loss_bad), jnp.float32(loss_good))
        o_ref[pl.ds(t, 1), :] = jnp.where(
            ul >= p, jnp.float32(1.0), jnp.float32(0.0)
        ).astype(o_ref.dtype)
        stay_bad = jnp.where(ut >= jnp.float32(p_bg), one, zero)
        go_bad = jnp.where(ut < jnp.float32(p_gb), one, zero)
        return jnp.where(bad, stay_bad, go_bad)

    # Loop only the true packet count: stepping the padding rows (discarded
    # by the wrapper's slice) would cost real wall-clock.
    jax.lax.fori_loop(0, n_valid, body, bad0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "p_gb", "p_bg", "loss_good", "loss_bad", "block_r", "interpret"
    ),
)
def burst_mask_kernel(
    u_init: jax.Array,   # (R,) uniform [0, 1): stationary initial state
    u_loss: jax.Array,   # (R, N) uniforms: per-packet loss draw
    u_tr: jax.Array,     # (R, N) uniforms: per-packet state transition
    *,
    p_gb: float,
    p_bg: float,
    loss_good: float,
    loss_bad: float,
    block_r: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(R, N) float32 Gilbert–Elliott packet keep-masks, bit-exact against
    ``ref.burst_mask_ref`` for identical uniforms.  Runs on the transposed
    (packets, chains) layout; ``block_r`` chains per lane block."""
    r, n = u_loss.shape
    br = block_r if r > block_r else -(-r // 128) * 128   # lane-dense block
    rp = -(-r // br) * br
    np_ = -(-n // 8) * 8                                  # sublane-align packets
    pad = lambda a, fill: jnp.pad(
        a.astype(jnp.float32).T, ((0, np_ - n), (0, rp - r)),
        constant_values=fill,
    )
    u_init = jnp.pad(u_init.astype(jnp.float32), (0, rp - r),
                     constant_values=1.0).reshape(1, rp)
    out = pl.pallas_call(
        functools.partial(
            _burst_mask_kernel,
            p_gb=p_gb, p_bg=p_bg, loss_good=loss_good, loss_bad=loss_bad,
            n_valid=n,
        ),
        grid=(rp // br,),
        in_specs=[
            pl.BlockSpec((1, br), lambda i: (0, i)),
            pl.BlockSpec((np_, br), lambda i: (0, i)),
            pl.BlockSpec((np_, br), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((np_, br), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((np_, rp), jnp.float32),
        interpret=pallas_interpret(interpret),
    )(u_init, pad(u_loss, 1.0), pad(u_tr, 1.0))
    return out[:n, :r].T


@functools.partial(
    jax.jit, static_argnames=("bits", "loss_rate", "block_t", "block_d", "interpret")
)
def lossy_link_egress_kernel(
    x: jax.Array,        # (T, D)
    u: jax.Array,        # (T, D) uniform [0, 1)
    s_min: jax.Array,    # (D,)
    s_max: jax.Array,    # (D,)
    *,
    bits: int,
    loss_rate: float,
    block_t: int = 256,
    block_d: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    t, d = x.shape
    bt = min(block_t, t)
    bd = min(block_d, d)
    pad_t = (-t) % bt
    pad_d = (-d) % bd
    if pad_t or pad_d:
        x = jnp.pad(x, ((0, pad_t), (0, pad_d)))
        u = jnp.pad(u, ((0, pad_t), (0, pad_d)), constant_values=1.0)
        s_min = jnp.pad(s_min, (0, pad_d))
        s_max = jnp.pad(s_max, (0, pad_d), constant_values=1.0)
    grid = (x.shape[0] // bt, x.shape[1] // bd)
    out = pl.pallas_call(
        functools.partial(_egress_kernel, bits=bits, loss_rate=loss_rate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bt, bd), lambda i, j: (i, j)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=pallas_interpret(interpret),
    )(x, u, s_min.reshape(1, -1), s_max.reshape(1, -1))
    return out[:t, :d]
