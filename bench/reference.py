"""Plain reference of the split model, for the comparison that decides
``correct``.

Written from the published descriptions alone, in ``jax.numpy`` at
float32 with matmuls at ``HIGHEST`` precision.  It imports nothing of the
program and takes nothing the program made: weights are drawn again from
the seed, one layer at a time, so that a reference of the 7B
configuration fits beside nothing else on one chip.

What is per architecture is in the architecture's module
(``bench/arch/<architectures[0]>.py``, see ``bench/model.py``): the
weights of a layer and of what lies outside the layers, one layer's
forward pass (``layer_forward``, given the layer's kind, which the
module may pick by index), and optionally the head.  Shared here by every architecture: the arithmetic (``mm``,
``ein``, ``rmsnorm``, ``rope``) and its float8 control; the COMtune link
(arXiv 2112.09407 Eq. 7, 8 and 12) and its Gilbert–Elliott channel; the
loop over the layers with the link after ``split_after_layers``; the
served-token gaps, with router near-ties resolved where a cell asks
(``_TieSearch``); the training loss, Adam and the per-leaf norms.

``prec="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 and every cotangent to e5m2, each with a per-tensor scale, the
precision below the bfloat16 the configurations state.

Departure from the published link, shared with the program: the link's
lost elements are zero in the code domain, so a lost element decodes to
the clip range's lower end (Eq. 12 taken literally).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import model
from bench.model import dims, weight_key

HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _cast(x, dtype):
    """``x`` rounded to a float8 ``dtype`` under a per-tensor scale that
    maps max|x| to the type's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _cast(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _cast(x, jnp.float8_e4m3fn), None


def _fp8_bwd(_, g):
    return (_cast(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _rounded(x, prec):
    """``prec="fp8"``: a careful float8 port — each operand in e4m3 and its
    cotangent in e5m2, each under its own per-tensor scale, so that no
    small gradient underflows."""
    return x if prec == "f32" else _fp8(x)


def mm(a, b, prec):
    return jnp.matmul(_rounded(a, prec), _rounded(b, prec), precision=HI)


def ein(spec, a, b, prec):
    return jnp.einsum(spec, _rounded(a, prec), _rounded(b, prec), precision=HI)


def rmsnorm(x, scale, eps):
    """RMS norm with the scale stored as ``w - 1``, as the program stores it."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def rope(x, pos, theta):
    """Rotate (B, S, N, hd) by half-split (rotate_half) RoPE."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def head_logits(x, outer, conf, prec):
    """Final norm and logits; an architecture may give its own."""
    x = rmsnorm(x, outer["final_norm"], conf["rms_norm_eps"])
    if "lm_head" in outer:
        return mm(x, outer["lm_head"], prec)
    return ein("bsd,vd->bsv", x, outer["embed"], prec)


def _head(conf):
    return getattr(model.arch(conf), "head_logits", head_logits)


# ---------------------------------------------------------------------------
# The link (Eq. 7, 12) and its Gilbert–Elliott channel
# ---------------------------------------------------------------------------

def ge_params(loss_rate, burst_len=4.0, loss_good=0.0, loss_bad=1.0):
    """Gilbert's construction: transition probabilities that give the
    stationary loss ``loss_rate`` with a mean burst of ``burst_len``
    packets.  Returns (p_gb, p_bg, loss_good, loss_bad, stationary loss)."""
    pi_b = min(max((loss_rate - loss_good) / (loss_bad - loss_good), 0.0), 0.999)
    p_bg = 1.0 / max(burst_len, 1.0)
    p_gb = p_bg * pi_b / max(1.0 - pi_b, 1e-9)
    if p_gb > 1.0:
        p_gb, p_bg = 1.0, (1.0 - pi_b) / pi_b
    pb = p_gb / (p_gb + p_bg)
    return p_gb, p_bg, loss_good, loss_bad, (1.0 - pb) * loss_good + pb * loss_bad


def ge_keep(key, n, per_packet, shuffle, ge):
    """Keep-mask of one message of ``n`` elements: a stationary start
    state, one Markov step per packet, packets of ``per_packet``
    consecutive elements, and the sender's interleaving permutation."""
    p_gb, p_bg, lg, lb, _ = ge
    kperm, kmask = jax.random.split(key)
    kinit, kloss, ktr = jax.random.split(kmask, 3)
    npk = -(-n // per_packet)
    u0 = jax.random.uniform(kinit, ())
    ul = jax.random.uniform(kloss, (npk,))
    ut = jax.random.uniform(ktr, (npk,))

    def step(bad, u):
        keep = (u[0] >= jnp.where(bad, jnp.float32(lb), jnp.float32(lg))).astype(jnp.float32)
        return jnp.where(bad, u[1] >= jnp.float32(p_bg), u[1] < jnp.float32(p_gb)), keep

    _, keep = jax.lax.scan(step, u0 < p_gb / max(p_gb + p_bg, 1e-12),
                           jnp.stack([ul, ut], -1))
    keep = jnp.repeat(keep, per_packet)[:n]
    if shuffle:
        keep = jnp.zeros((n,), jnp.float32).at[jax.random.permutation(kperm, n)].set(keep)
    return keep


def quant_code(x, conf):
    lo, hi = conf["link"]["clip"]
    levels = 2.0 ** conf["link"]["quant_bits"] - 1
    return jnp.round((jnp.clip(x, lo, hi) - lo) / (hi - lo) * levels)


def dequant(code, conf):
    lo, hi = conf["link"]["clip"]
    return code / (2.0 ** conf["link"]["quant_bits"] - 1) * (hi - lo) + lo


def serve_link(x, keep, conf, loss):
    """Eq. 12: quantise, lose elements, compensate by 1/(1-p), decode."""
    return dequant(quant_code(x, conf) * keep / (1.0 - loss), conf)


def train_link(x, keep, conf):
    """Eq. 8 with Eq. 7: a straight-through quantise round trip, then
    inverted dropout at rate r."""
    r = conf["link"]["dropout_rate"]
    a = x + jax.lax.stop_gradient(dequant(quant_code(x, conf), conf) - x)
    return jnp.where(keep, a / (1.0 - r), 0.0)


# ---------------------------------------------------------------------------
# Weights, as served (bfloat16 values held in float32)
# ---------------------------------------------------------------------------

def _as_served(tree, conf):
    dt = jnp.dtype(conf["torch_dtype"])
    return jax.tree_util.tree_map(lambda a: a.astype(dt).astype(jnp.float32), tree)


@functools.lru_cache(maxsize=8)
def _layer_fn(conf_key):
    conf = json.loads(conf_key)
    return jax.jit(
        lambda k, kind: _as_served(model.arch(conf).layer_weights(k, conf, kind), conf),
        static_argnums=1)


def _conf_key(conf):
    """A configuration as a hashable static argument: its JSON text."""
    return json.dumps(conf, sort_keys=True)


def layer_at(conf, seed, i):
    return _layer_fn(_conf_key(conf))(model.layer_key(weight_key(seed), i),
                                      model.layer_kind(conf, i))


@functools.lru_cache(maxsize=8)
def _outer_fn(conf_key):
    conf = json.loads(conf_key)
    return jax.jit(lambda k: _as_served(model.arch(conf).outer_weights(k, conf), conf))


def outer_at(conf, seed):
    return _outer_fn(_conf_key(conf))(model.outer_key(weight_key(seed)))


def all_weights(conf, seed):
    """Every weight, layers stacked on a leading axis (training), which
    takes layers of one kind."""
    m = dims(conf)
    if len({model.layer_kind(conf, i) for i in range(m["layers"])}) > 1:
        raise ValueError(f"{conf['name']}: training's reference stacks the layers, "
                         "which are not all of one kind")
    layers = [layer_at(conf, seed, i) for i in range(m["layers"])]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)
    return {"layers": stacked, **outer_at(conf, seed)}


# ---------------------------------------------------------------------------
# Serving: logits over prompt + served tokens
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _apply_layer(x, w, conf_key, prec, kind):
    conf = json.loads(conf_key)
    return model.arch(conf).layer_forward(x, w, conf, prec, kind)


def serve_hidden(conf, seed, tokens, keep, loss, prec="f32"):
    """Final hidden states (R, L, d) of sequences ``tokens`` (R, L) whose
    split activation crosses the link with keep-mask ``keep`` (R, L, d)."""
    m = dims(conf)
    ck = _conf_key(conf)
    outer = outer_at(conf, seed)
    x = jnp.take(outer["embed"], jnp.asarray(tokens), axis=0)
    for i in range(m["layers"]):
        if i == m["split"]:
            x = serve_link(x, keep, conf, loss)
        x = _apply_layer(x, layer_at(conf, seed, i), ck, prec, model.layer_kind(conf, i))
    return x, outer


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(x_ref, x_ctl, outer, conf_key, with_control, served, pos_mask):
    """Per position: how far the served token's reference logit lies below
    the reference's best, and the same for the control's first choice."""
    conf = json.loads(conf_key)
    ref = _head(conf)(x_ref[None], outer, conf, "f32")[0]
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    out = {"served": jnp.max(jnp.where(pos_mask, gap, 0.0))}
    if with_control:
        ctl = _head(conf)(x_ctl[None], outer, conf, "fp8")[0]
        pick = jnp.argmax(ctl, -1)
        cgap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        out["control"] = jnp.max(jnp.where(pos_mask, cgap, 0.0))
    return out


@functools.partial(jax.jit, static_argnums=1)
def _prefill_keys(keys, length):
    """Each prompt position's link key: ``fold_in(key, i)``, the raw key at 0."""
    def one(k):
        ks = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(length, dtype=jnp.uint32))
        return ks.at[0].set(k)
    return jax.vmap(one)(keys)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _keep_masks(keys, n, per_packet, shuffle, ge):
    return jax.vmap(jax.vmap(lambda k: ge_keep(k, n, per_packet, shuffle, ge)))(keys)


def _served_at(r, length):
    """Each position's served token and the mask of served positions."""
    p, n = len(r["prompt"]), len(r["tokens"])
    served = np.zeros((length,), np.int32)
    served[p - 1: p - 1 + n] = r["tokens"]
    pos = np.zeros((length,), bool)
    pos[p - 1: p - 1 + n] = True
    return served, pos


def serve_gaps(conf, seed, sample, loss, ge, shape, with_control=False, gap=None):
    """Widest gaps over a sample of served requests.

    ``sample`` holds per request: ``prompt`` (P,), ``tokens`` (n,) served,
    ``prefill_key`` and ``round_keys`` (n - 1, 2): the link key of each
    prompt position is ``fold_in(prefill_key, i)`` (the raw key at 0), and
    decode round j feeds ``tokens[j]`` at position P + j under
    ``round_keys[j]``.  Position P - 1 + j predicts ``tokens[j]``.
    Sequences are padded to ``shape`` = (rows, positions), the same for
    every run of a cell, so that each program compiles once.

    ``gap`` is the cell's ``served_gap`` entry of its limits file.  Where
    it has ``router_tie``, router near-ties are resolved (``_TieSearch``)
    and the plain gaps and the flips taken are returned beside the
    resolved ones; without it no search runs."""
    m = dims(conf)
    pk = conf["link"]
    seqs = [np.concatenate([r["prompt"], r["tokens"][:-1]]) for r in sample]
    rows, length = shape
    assert len(sample) <= rows and max(len(s) for s in seqs) <= length
    tokens = np.zeros((rows, length), np.int32)
    pre = np.zeros((rows, 2), np.uint32)
    for j, r in enumerate(sample):
        pre[j] = r["prefill_key"]
    keys = np.array(_prefill_keys(jnp.asarray(pre), length))
    for j, (r, s) in enumerate(zip(sample, seqs)):
        tokens[j, : len(s)] = s
        keys[j, len(r["prompt"]): len(s)] = np.asarray(r["round_keys"])
    keep = _keep_masks(jnp.asarray(keys), m["d"], pk["elements_per_packet"],
                       pk["shuffle"], ge)
    tie = (gap or {}).get("router_tie")
    with jax.default_matmul_precision("highest"):
        if tie is not None:
            x_ctl = (serve_hidden(conf, seed, tokens, keep, loss, "fp8")[0]
                     if with_control else None)
            return _TieSearch(conf, seed, tokens, keep, loss, gap["limit"], tie).run(
                sample, x_ctl)
        x_ref, outer = serve_hidden(conf, seed, tokens, keep, loss, "f32")
        x_ctl = (serve_hidden(conf, seed, tokens, keep, loss, "fp8")[0]
                 if with_control else x_ref)
        ck = _conf_key(conf)
        worst = {"served": 0.0, "control": 0.0}
        for j, r in enumerate(sample):
            served, pos = _served_at(r, length)
            g = _gaps(x_ref[j], x_ctl[j], outer, ck, with_control,
                      jnp.asarray(served), jnp.asarray(pos))
            for name, v in g.items():
                worst[name] = max(worst[name], float(v))
    if not with_control:
        worst.pop("control")
    return worst


# ---------------------------------------------------------------------------
# Serving: router near-ties
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(3, 4))
def _apply_layer_flipped(x, w, flips, conf_key, kind):
    conf = json.loads(conf_key)
    return model.arch(conf).layer_forward(x, w, conf, "f32", kind, flips=flips)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _route_margins(x, w, conf_key, kind):
    conf = json.loads(conf_key)
    return model.arch(conf).route_margins(x, w, conf, kind)


@functools.partial(jax.jit, static_argnums=2)
def _row_gaps(x, outer, conf_key, picks):
    """Per position of one row (1, L, d): how far the picked token's
    reference logit lies below the reference's best."""
    conf = json.loads(conf_key)
    ref = _head(conf)(x, outer, conf, "f32")[0]
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, picks[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnums=2)
def _control_picks(x_ctl, outer, conf_key):
    """The float8 control's first choice at each position of one row."""
    conf = json.loads(conf_key)
    return jnp.argmax(_head(conf)(x_ctl[None], outer, conf, "fp8")[0], -1).astype(jnp.int32)


class _TieSearch:
    """The f32 reference, one sampled row at a time, with router near-ties
    resolved.

    Where the served path (bfloat16) and the reference (float32) see two
    experts' selection scores within rounding of each other, they may
    choose differently; that is no error, but it moves the position's
    logits as far as a wrong program would.  So at each served position,
    in order, whose gap exceeds ``limit``: one flip (``layer_forward``'s
    ``flips``: the first unchosen expert in place of the last chosen) is
    tried at that position in each router layer whose margin
    (``route_margins``) is under ``tie["tie_margin"]``, smallest first;
    the first that brings the gap to or under the limit is kept in force
    for the rest of the row.  At most ``tie["max_flips"]`` are kept over
    the whole sample; a gap that no flip explains stays as it is."""

    def __init__(self, conf, seed, tokens, keep, loss, limit, tie):
        self.conf, self.seed, self.tokens, self.keep, self.loss = conf, seed, tokens, keep, loss
        self.limit = float(limit)
        self.margin, self.max_flips = float(tie["tie_margin"]), int(tie["max_flips"])
        self.ck = _conf_key(conf)
        if not hasattr(model.arch(conf), "route_margins"):
            raise ValueError(f"{conf['name']}: router_tie is set, but the architecture "
                             "gives no route_margins")
        self.outer = outer_at(conf, seed)

    def _run(self, j, start, x, flips, inputs, margins):
        """Layers ``start``.. of row ``j`` from ``x``, the input of layer
        ``start``; fills ``inputs`` and ``margins`` from there on and
        returns the final hidden states (1, L, d)."""
        m = dims(self.conf)
        for i in range(start, m["layers"]):
            if i == m["split"] and i > start:
                x = serve_link(x, self.keep[j: j + 1], self.conf, self.loss)
            w = layer_at(self.conf, self.seed, i)
            kind = model.layer_kind(self.conf, i)
            inputs[i] = x
            mg = _route_margins(x, w, self.ck, kind)
            margins[i] = None if mg is None else np.asarray(mg)
            f = None if mg is None else jnp.asarray(flips[i])
            x = _apply_layer_flipped(x, w, f, self.ck, kind)
        return x

    def _plain(self, j):
        m = dims(self.conf)
        x = jnp.take(self.outer["embed"], jnp.asarray(self.tokens[j: j + 1]), axis=0)
        if m["split"] == 0:
            x = serve_link(x, self.keep[j: j + 1], self.conf, self.loss)
        inputs, margins = [None] * m["layers"], [None] * m["layers"]
        flips = [np.zeros((1, self.tokens.shape[1]), bool)] * m["layers"]
        final = self._run(j, 0, x, flips, inputs, margins)
        return final, flips, inputs, margins

    def _resolve(self, j, plain, picks, pos, budget):
        """Plain and resolved gaps of row ``j`` for the tokens ``picks`` at
        the positions ``pos``, and the flips kept (at most ``budget``)."""
        final, flips, inputs, margins = plain
        inputs, margins = list(inputs), list(margins)
        picks = jnp.asarray(picks)
        gaps = np.asarray(_row_gaps(final, self.outer, self.ck, picks))
        first, kept = float(np.max(gaps, where=pos, initial=0.0)), 0
        for p in np.flatnonzero(pos):
            if gaps[p] <= self.limit:
                continue
            if kept >= budget:
                break
            near = sorted((mg[0, p], i) for i, mg in enumerate(margins)
                          if mg is not None and mg[0, p] < self.margin)
            for _, i in near:
                f = list(flips)
                f[i] = flips[i].copy()
                f[i][0, p] = True
                ins, mgs = list(inputs), list(margins)
                x = self._run(j, i, inputs[i], f, ins, mgs)
                g = np.asarray(_row_gaps(x, self.outer, self.ck, picks))
                if g[p] <= self.limit:
                    flips, inputs, margins, gaps, kept = f, ins, mgs, g, kept + 1
                    break
        return first, float(np.max(gaps, where=pos, initial=0.0)), kept

    def run(self, sample, x_ctl):
        length = self.tokens.shape[1]
        out = {"served": 0.0, "served_plain": 0.0, "router_flips": 0}
        if x_ctl is not None:
            out.update(control=0.0, control_plain=0.0, control_flips=0)
        for j, r in enumerate(sample):
            plain = self._plain(j)
            served, pos = _served_at(r, length)
            todo = [("served", "router_flips", served)]
            if x_ctl is not None:
                todo.append(("control", "control_flips",
                             _control_picks(x_ctl[j], self.outer, self.ck)))
            for name, count, picks in todo:
                first, resolved, kept = self._resolve(
                    j, plain, picks, pos, self.max_flips - out[count])
                out[name] = max(out[name], resolved)
                out[name + "_plain"] = max(out[name + "_plain"], first)
                out[count] += kept
        return out


# ---------------------------------------------------------------------------
# Training: loss, gradients and Adam, as the optimizer gets them
# ---------------------------------------------------------------------------

def _train_loss_sum(params, tokens, keep, conf, prec):
    """Summed next-token loss, the layers stacked and scanned: one kind of
    layer only (``all_weights``)."""
    m = dims(conf)
    x = jnp.take(params["embed"], tokens, axis=0)
    lay = params["layers"]
    layer, kind = model.arch(conf).layer_forward, model.layer_kind(conf, 0)

    def run(x, lo, hi):
        seg = jax.tree_util.tree_map(lambda a: a[lo:hi], lay)
        body = jax.checkpoint(lambda x, w: (layer(x, w, conf, prec, kind), None))
        return jax.lax.scan(body, x, seg)[0]

    x = run(x, 0, m["split"])
    x = train_link(x, keep, conf)
    x = run(x, m["split"], m["layers"])
    logits = _head(conf)(x, params, conf, prec)[:, :-1]
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(lse - tgt)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _grad_rows(params, tokens, keep, conf_key, prec):
    return jax.value_and_grad(_train_loss_sum)(params, tokens, keep, json.loads(conf_key), prec)


def train_steps(conf, seed, batches, key, opt, n_steps, prec="f32",
                rows_per_block=2, half_batch=False):
    """Follow ``n_steps`` optimizer steps from the seed's weights.

    ``batches`` (K, B, S) int32; ``key`` is the key handed to the epoch,
    split once per step (``key, sub = split(key)``), ``sub`` drawing the
    Eq. 7 keep-mask over the whole (B, S, d) split activation.  Gradients
    are summed over blocks of ``rows_per_block`` rows.  ``half_batch``
    plants a fault: the loss is the mean over the first half of the rows.
    Returns each step's loss; per-leaf norms of the first gradient, of
    Adam's first moment and of the parameters' change after the last step;
    and the first moment itself, on the host."""
    m = dims(conf)
    ck = _conf_key(conf)
    r = conf["link"]["dropout_rate"]
    params = all_weights(conf, seed)
    start = jax.tree_util.tree_map(jnp.copy, params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    b1, b2, eps, lr, clip = opt["b1"], opt["b2"], opt["eps"], opt["lr"], opt["clip_norm"]
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for t in range(1, n_steps + 1):
            key, sub = jax.random.split(key)
            tokens = jnp.asarray(batches[t - 1])
            b, s = tokens.shape
            keep = jax.random.bernoulli(sub, 1.0 - r, (b, s, m["d"]))
            rows = b // 2 if half_batch else b
            total, grads = 0.0, None
            for lo in range(0, rows, rows_per_block):
                hi = min(lo + rows_per_block, rows)
                val, g = _grad_rows(params, tokens[lo:hi], keep[lo:hi], ck, prec)
                total = total + val
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
            count = rows * (s - 1)
            grads = jax.tree_util.tree_map(lambda g: g / count, grads)
            losses.append(float(total) / count)
            params, mu, nu, g_norms = _adam(params, grads, mu, nu, jnp.float32(t), b1, b2,
                                            eps, lr, clip, conf["torch_dtype"])
            if first_grad is None:
                first_grad = g_norms
    return {
        "loss": losses,
        "grad": first_grad,
        "moment": leaf_norms(mu),
        "moment_tree": jax.device_get(mu),
        "change": leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, start)),
    }


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10))
def _adam(params, grads, mu, nu, t, b1, b2, eps, lr, clip, dtype):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    grads = jax.tree_util.tree_map(
        lambda g: g * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)), grads)
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps))
        .astype(dtype).astype(jnp.float32), params, mu, nu)
    return params, mu, nu, _norms(grads)


def _norms(tree):
    """Norm of each leaf; stacked layer leaves give one norm per layer."""
    out = {}
    for name, a in tree.items():
        if name == "layers":
            for leaf, v in a.items():
                out[leaf] = jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(a)))[None]
    return out


_norms_jit = jax.jit(_norms)


def leaf_norms(tree):
    return {k: np.asarray(v, np.float64) for k, v in _norms_jit(tree).items()}
