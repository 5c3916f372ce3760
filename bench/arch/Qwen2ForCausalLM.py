"""Qwen2 (``Qwen2ForCausalLM``): every layer a pre-norm decoder layer with
grouped-query attention (q, k and v projections with biases, half-split
RoPE) and a SwiGLU MLP; embedding optionally tied to the head.

The architecture's part of the benchmark (``bench/model.py`` lists what
such a module provides): the program's configuration and parameter tree,
the reference's weights and layer, and the operations and bytes the work
needs.  The reference layer is written from Hugging Face's description of
``Qwen2ForCausalLM`` alone.  Departures from it, shared with the program:
the norm scale is stored as ``w - 1`` (the shared ``rmsnorm`` computes
``x * (1 + scale)``); the weights are random from the seed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import flops, model
from bench.reference import ein, mm, rmsnorm, rope

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                "ln2", "w_gate", "w_up", "w_down")
# The program's parameter tree, leaf by leaf, under the reference's names.
UNIT_LEAVES = {("norm1", "scale"): "ln1", ("mix", "wq"): "wq", ("mix", "bq"): "bq",
               ("mix", "wk"): "wk", ("mix", "bk"): "bk", ("mix", "wv"): "wv",
               ("mix", "bv"): "bv", ("mix", "w_out"): "wo", ("norm2", "scale"): "ln2",
               ("ffn", "w_gate"): "w_gate", ("ffn", "w_up"): "w_up",
               ("ffn", "w_down"): "w_down"}


def dims(conf: dict) -> dict:
    m = model.dims(conf)
    h = conf["num_attention_heads"]
    return dict(m, h=h, kv=conf["num_key_value_heads"], hd=m["d"] // h,
                ff=conf["intermediate_size"])


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

def program_config(conf: dict, link: dict, remat: bool = True):
    from repro.configs.base import LayerSpec, ModelConfig

    m = dims(conf)
    return ModelConfig(
        name=conf["name"], arch_type="dense", source=conf["source"],
        num_layers=m["layers"], d_model=m["d"], num_heads=m["h"],
        num_kv_heads=m["kv"], d_ff=m["ff"], vocab_size=m["vocab"],
        qkv_bias=True, act=conf["hidden_act"], gated_mlp=True,
        norm="rmsnorm", rope_theta=float(conf["rope_theta"]),
        tie_embeddings=m["tied"], unit_pattern=(LayerSpec(kind="attn"),),
        link=model.link_config(conf, link),
        dtype=conf["torch_dtype"], remat=remat,
    )


def program_tree(conf: dict, key) -> dict:
    m = dims(conf)
    dtype = jnp.dtype(conf["torch_dtype"])
    keys = jax.vmap(lambda i: model.layer_key(key, i))(
        jnp.arange(m["layers"], dtype=jnp.uint32))
    stacked = jax.lax.map(
        lambda k: {n: a.astype(dtype) for n, a in layer_weights(k, conf, 0).items()},
        keys)
    outer = outer_weights(model.outer_key(key), conf)
    unit = {
        "norm1": {"scale": stacked["ln1"]},
        "mix": {"wq": stacked["wq"], "wk": stacked["wk"], "wv": stacked["wv"],
                "w_out": stacked["wo"], "bq": stacked["bq"],
                "bk": stacked["bk"], "bv": stacked["bv"]},
        "norm2": {"scale": stacked["ln2"]},
        "ffn": {"w_up": stacked["w_up"], "w_down": stacked["w_down"],
                "w_gate": stacked["w_gate"]},
    }
    tree = {
        "embed": outer["embed"].astype(dtype),
        "stack": {"prologue": [], "units": [unit]},
        "final_norm": {"scale": outer["final_norm"].astype(dtype)},
    }
    if "lm_head" in outer:
        tree["lm_head"] = outer["lm_head"].astype(dtype)
    return tree


def canonical(tree) -> dict:
    """The program's parameter-shaped tree under the reference's names,
    layers stacked on the leading axis."""
    unit = tree["stack"]["units"][0]
    out = {"layers": {name: unit[a][b] for (a, b), name in UNIT_LEAVES.items()},
           "embed": tree["embed"], "final_norm": tree["final_norm"]["scale"]}
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"]
    return out


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------

def layer_weights(key, conf: dict, kind) -> dict:
    """One decoder layer's weights in float32 (before the cast to the
    served dtype).  Every layer is of one kind."""
    m = dims(conf)
    d, q, kv, ff = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["ff"]
    ks = dict(zip(LAYER_LEAVES, jax.random.split(key, len(LAYER_LEAVES))))
    shapes = {
        "ln1": ((d,), model.NORM_STD), "ln2": ((d,), model.NORM_STD),
        "wq": ((d, q), d ** -0.5), "bq": ((q,), model.BIAS_STD),
        "wk": ((d, kv), d ** -0.5), "bk": ((kv,), model.BIAS_STD),
        "wv": ((d, kv), d ** -0.5), "bv": ((kv,), model.BIAS_STD),
        "wo": ((q, d), q ** -0.5),
        "w_gate": ((d, ff), d ** -0.5), "w_up": ((d, ff), d ** -0.5),
        "w_down": ((ff, d), ff ** -0.5),
    }
    return {n: model.normal(ks[n], s, std) for n, (s, std) in shapes.items()}


def outer_weights(key, conf: dict) -> dict:
    """Embedding, final norm scale and (untied) head, float32."""
    m = dims(conf)
    ke, kn, kh = jax.random.split(key, 3)
    out = {
        "embed": model.normal(ke, (m["vocab"], m["d"]), model.EMBED_STD),
        "final_norm": model.normal(kn, (m["d"],), model.NORM_STD),
    }
    if not m["tied"]:
        out["lm_head"] = model.normal(kh, (m["d"], m["vocab"]), m["d"] ** -0.5)
    return out


def layer_forward(x, w, conf, prec, kind):
    """One pre-norm Qwen2 layer over full causal sequences (B, S, d)."""
    m = dims(conf)
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
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = ein("bnqk,bknd->bqnd", probs, v, prec).reshape(b, s, m["h"] * m["hd"])
    x = x + mm(att, w["wo"], prec)
    h = rmsnorm(x, w["ln2"], eps)
    up = jax.nn.silu(mm(h, w["w_gate"], prec)) * mm(h, w["w_up"], prec)
    return x + mm(up, w["w_down"], prec)


# ---------------------------------------------------------------------------
# Operations and bytes (bench/flops.py forwards to these): the four the
# benchmark reads, param_count, train_step_flops, decode_steps and
# decode_attention, and their parts
# ---------------------------------------------------------------------------

def layer_matmul_params(conf: dict) -> int:
    m = dims(conf)
    q, kv = m["h"] * m["hd"], m["kv"] * m["hd"]
    return m["d"] * (q + 2 * kv) + q * m["d"] + 3 * m["d"] * m["ff"]


def layer_params(conf: dict) -> int:
    m = dims(conf)
    q, kv = m["h"] * m["hd"], m["kv"] * m["hd"]
    return layer_matmul_params(conf) + (q + 2 * kv) + 2 * m["d"]


def param_count(conf: dict) -> int:
    m = dims(conf)
    table = m["vocab"] * m["d"] * (1 if m["tied"] else 2)
    return m["layers"] * layer_params(conf) + table + m["d"] + 2 * m["d"]


def head_params(conf: dict) -> int:
    m = dims(conf)
    return m["vocab"] * m["d"]


def matmul_params(conf: dict) -> int:
    return dims(conf)["layers"] * layer_matmul_params(conf) + head_params(conf)


def attention_flops(conf: dict, rows: float) -> float:
    m = dims(conf)
    return 4.0 * m["h"] * m["hd"] * rows * m["layers"]


def train_step_flops(conf: dict, batch: int, seq: int) -> float:
    causal_rows = batch * seq * (seq + 1) / 2
    return 6.0 * matmul_params(conf) * batch * seq + 3.0 * attention_flops(conf, causal_rows)


def kv_row_bytes(conf: dict) -> int:
    m = dims(conf)
    return 2 * m["kv"] * m["hd"] * flops.itemsize(conf)


def kv_read_bytes(conf: dict, valid_rows: float) -> float:
    return valid_rows * kv_row_bytes(conf) * dims(conf)["layers"]


def weight_read_bytes(conf: dict) -> float:
    m = dims(conf)
    return (m["layers"] * layer_params(conf) + head_params(conf) + m["d"]) * flops.itemsize(conf)


def decode_steps(conf: dict, steps: int, counters: dict) -> tuple:
    """Every weight is read by every step, whatever the routing: the
    counters beyond live slots and valid rows are not needed."""
    live, rows = counters["live_slot_steps"], counters["valid_rows"]
    f = 2.0 * matmul_params(conf) * live + attention_flops(conf, rows)
    return f, weight_read_bytes(conf) * steps + kv_read_bytes(conf, rows)


def decode_attention(conf: dict, live: float, valid_rows: float) -> tuple:
    m = dims(conf)
    qo = 2.0 * live * m["h"] * m["hd"] * flops.itemsize(conf) * m["layers"]
    return attention_flops(conf, valid_rows), kv_read_bytes(conf, valid_rows) + qo
