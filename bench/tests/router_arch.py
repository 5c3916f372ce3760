"""A made-up architecture with routed experts: Qwen2 layers whose
feed-forward is the program's own expert layer (``models/moe.py``):
softmax router, top ``num_experts_per_tok`` of ``num_experts``, gates
renormalised over the chosen experts.  ``capacity_factor`` is
``num_experts / top_k``, so every expert can take every token and none is
dropped.

A test copies this file into a tiny checkout as
``bench/arch/MadeUpRouterForCausalLM.py``.  Its reference gives both
hooks of a routed layer (``bench/model.py``): ``route_margins`` and
``layer_forward``'s ``flips``.  ``param_count`` counts its experts; its
other counts are Qwen2's dense ones, and no test reads them.
"""

import dataclasses
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import model
from bench.reference import ein, mm, rmsnorm, rope

base = model.load_arch(str(Path(__file__).resolve().parents[2]), "Qwen2ForCausalLM")
ATTENTION = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2")


def dims(conf):
    return dict(base.dims(conf), e=conf["num_experts"], k=conf["num_experts_per_tok"],
                f=conf["moe_intermediate_size"])


def program_config(conf, link, remat=True):
    from repro.configs.base import LayerSpec

    m = dims(conf)
    return dataclasses.replace(
        base.program_config(conf, link, remat), arch_type="moe",
        unit_pattern=(LayerSpec(kind="attn", moe=True),), num_experts=m["e"],
        top_k=m["k"], moe_dff=m["f"], capacity_factor=m["e"] / m["k"])


def program_tree(conf, key):
    m = dims(conf)
    dtype = jnp.dtype(conf["torch_dtype"])
    keys = jax.vmap(lambda i: model.layer_key(key, i))(
        jnp.arange(m["layers"], dtype=jnp.uint32))
    w = jax.lax.map(
        lambda k: {n: a.astype(dtype) for n, a in layer_weights(k, conf, 0).items()}, keys)
    outer = base.outer_weights(model.outer_key(key), conf)
    unit = {
        "norm1": {"scale": w["ln1"]},
        "mix": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "w_out": w["wo"],
                "bq": w["bq"], "bk": w["bk"], "bv": w["bv"]},
        "norm2": {"scale": w["ln2"]},
        "ffn": {"router": w["router"], "w_gate": w["e_gate"], "w_up": w["e_up"],
                "w_down": w["e_down"]},
    }
    tree = {"embed": outer["embed"].astype(dtype),
            "stack": {"prologue": [], "units": [unit]},
            "final_norm": {"scale": outer["final_norm"].astype(dtype)}}
    if "lm_head" in outer:
        tree["lm_head"] = outer["lm_head"].astype(dtype)
    return tree


def layer_weights(key, conf, kind):
    """Qwen2's attention and norms; a router and the experts in place of
    its MLP."""
    m = dims(conf)
    d, e, f = m["d"], m["e"], m["f"]
    w = {n: a for n, a in base.layer_weights(key, conf, kind).items() if n in ATTENTION}
    kr, kg, ku, kd = jax.random.split(jax.random.fold_in(key, 1), 4)
    w.update(router=model.normal(kr, (d, e), d ** -0.5),
             e_gate=model.normal(kg, (e, d, f), d ** -0.5),
             e_up=model.normal(ku, (e, d, f), d ** -0.5),
             e_down=model.normal(kd, (e, f, d), f ** -0.5))
    return w


outer_weights = base.outer_weights


def _attended(x, w, conf, prec):
    """The residual stream after Qwen2's attention sublayer."""
    m = dims(conf)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h = rmsnorm(x, w["ln1"], conf["rms_norm_eps"])
    q = (mm(h, w["wq"], prec) + w["bq"]).reshape(b, s, m["h"], m["hd"])
    k = (mm(h, w["wk"], prec) + w["bk"]).reshape(b, s, m["kv"], m["hd"])
    v = (mm(h, w["wv"], prec) + w["bv"]).reshape(b, s, m["kv"], m["hd"])
    q, k = rope(q, pos, conf["rope_theta"]), rope(k, pos, conf["rope_theta"])
    g = m["h"] // m["kv"]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = ein("bqnd,bknd->bnqk", q, k, prec) / math.sqrt(m["hd"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = ein("bnqk,bknd->bqnd", probs, v, prec).reshape(b, s, m["h"] * m["hd"])
    return x + mm(att, w["wo"], prec)


def _ranked(h, w, conf, prec):
    """The router's probabilities of its top k + 1 experts, and their ids."""
    probs = jax.nn.softmax(mm(h, w["router"], prec), axis=-1)
    return jax.lax.top_k(probs, dims(conf)["k"] + 1)


def route_margins(x, w, conf, kind):
    m = dims(conf)
    h = rmsnorm(_attended(x, w, conf, "f32"), w["ln2"], conf["rms_norm_eps"])
    p, _ = _ranked(h, w, conf, "f32")
    return p[..., m["k"] - 1] - p[..., m["k"]]


def layer_forward(x, w, conf, prec, kind, flips=None):
    """Attention, then the top-k experts, gates renormalised over the
    chosen; where ``flips`` marks a position, the (k+1)-th expert stands
    in for the k-th."""
    m = dims(conf)
    k = m["k"]
    x = _attended(x, w, conf, prec)
    h = rmsnorm(x, w["ln2"], conf["rms_norm_eps"])
    p, ids = _ranked(h, w, conf, prec)
    if flips is not None:
        swap = flips[..., None] & (jnp.arange(k + 1) == k - 1)
        p = jnp.where(swap, p[..., k:], p)
        ids = jnp.where(swap, ids[..., k:], ids)
    p, ids = p[..., :k], ids[..., :k]
    gates = p / jnp.sum(p, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(ids, m["e"]) * gates[..., None], -2)      # (B, S, E)
    up = jax.nn.silu(ein("bsd,edf->bsef", h, w["e_gate"], prec)) * ein(
        "bsd,edf->bsef", h, w["e_up"], prec)
    out = ein("bsef,efd->bsed", up, w["e_down"], prec)
    return x + jnp.sum(out * weight[..., None], -2)


def param_count(conf):
    m = dims(conf)
    dense = 3 * m["d"] * m["ff"]
    routed = m["d"] * m["e"] + 3 * m["e"] * m["d"] * m["f"]
    return base.param_count(conf) + m["layers"] * (routed - dense)


train_step_flops = base.train_step_flops
decode_steps = base.decode_steps
decode_attention = base.decode_attention
