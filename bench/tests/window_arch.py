"""A made-up architecture of two kinds of layer, picked by index: Qwen2
layers, the first with full causal attention (the program's prologue),
the others attending to the last ``WINDOW`` positions only.

A test copies this file into a tiny checkout as
``bench/arch/MadeUpWindowForCausalLM.py``.  Its counts are Qwen2's: the
window's shorter reads are not counted, and no test reads them.
"""

import dataclasses
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import model
from bench.reference import ein, mm, rmsnorm, rope

WINDOW = 8
base = model.load_arch(str(Path(__file__).resolve().parents[2]), "Qwen2ForCausalLM")


def layer_kind(conf, i):
    return "full" if i == 0 else "window"


def program_config(conf, link, remat=True):
    from repro.configs.base import LayerSpec

    cfg = base.program_config(conf, link, remat)
    return dataclasses.replace(
        cfg, prologue=(LayerSpec(kind="attn"),),
        unit_pattern=(LayerSpec(kind="attn", window=WINDOW),),
        link=dataclasses.replace(cfg.link, split_after_units=cfg.link.split_after_units - 1))


def program_tree(conf, key):
    tree = base.program_tree(conf, key)
    unit = tree["stack"]["units"][0]
    tree["stack"] = {"prologue": [jax.tree_util.tree_map(lambda a: a[0], unit)],
                     "units": [jax.tree_util.tree_map(lambda a: a[1:], unit)]}
    return tree


layer_weights = base.layer_weights
outer_weights = base.outer_weights


def layer_forward(x, w, conf, prec, kind):
    """Qwen2's layer; a ``window`` layer's query at p sees keys p - WINDOW < k <= p."""
    m = base.dims(conf)
    b, s, _ = x.shape
    eps = conf["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h = rmsnorm(x, w["ln1"], eps)
    q = (mm(h, w["wq"], prec) + w["bq"]).reshape(b, s, m["h"], m["hd"])
    k = (mm(h, w["wk"], prec) + w["bk"]).reshape(b, s, m["kv"], m["hd"])
    v = (mm(h, w["wv"], prec) + w["bv"]).reshape(b, s, m["kv"], m["hd"])
    q, k = rope(q, pos, conf["rope_theta"]), rope(k, pos, conf["rope_theta"])
    g = m["h"] // m["kv"]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = ein("bqnd,bknd->bnqk", q, k, prec) / math.sqrt(m["hd"])
    qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    visible = kp <= qp
    if kind == "window":
        visible &= qp - kp < WINDOW
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    att = ein("bnqk,bknd->bqnd", probs, v, prec).reshape(b, s, m["h"] * m["hd"])
    x = x + mm(att, w["wo"], prec)
    h = rmsnorm(x, w["ln2"], eps)
    up = jax.nn.silu(mm(h, w["w_gate"], prec)) * mm(h, w["w_up"], prec)
    return x + mm(up, w["w_down"], prec)


param_count = base.param_count
train_step_flops = base.train_step_flops
decode_steps = base.decode_steps
decode_attention = base.decode_attention
