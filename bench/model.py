"""A configuration file as the program runs it, and its seeded weights.

The configuration files under ``bench/configs`` use the keys of the
model's published ``config.json``.  This module turns one into the
program's ``ModelConfig`` (the system under test) and makes the weights
from ``--seed``.  Weights are drawn layer by layer from
``fold_in(key, layer)``, so the plain reference (``bench/reference.py``)
can draw any one layer again without the program and without holding
the whole model.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                "ln2", "w_gate", "w_up", "w_down")
# Spread of the random weights.  Matrices use 1/sqrt(fan_in); biases and
# norm scales are small but non-zero so that a path that drops them shows.
BIAS_STD, NORM_STD, EMBED_STD = 0.1, 0.1, 0.02


def dims(conf: dict) -> dict:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": conf["num_key_value_heads"], "hd": d // h,
        "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
        "layers": conf["num_hidden_layers"],
        "split": conf["link"]["split_after_layers"],
        "tied": bool(conf["tie_word_embeddings"]),
    }


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, including ones over 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weight_key(seed: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), 0x5EED)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def layer_weights(key, conf: dict) -> dict:
    """One decoder layer's weights in float32 (before the cast to the
    served dtype)."""
    m = dims(conf)
    d, q, kv, ff = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["ff"]
    ks = dict(zip(LAYER_LEAVES, jax.random.split(key, len(LAYER_LEAVES))))
    shapes = {
        "ln1": ((d,), NORM_STD), "ln2": ((d,), NORM_STD),
        "wq": ((d, q), d ** -0.5), "bq": ((q,), BIAS_STD),
        "wk": ((d, kv), d ** -0.5), "bk": ((kv,), BIAS_STD),
        "wv": ((d, kv), d ** -0.5), "bv": ((kv,), BIAS_STD),
        "wo": ((q, d), q ** -0.5),
        "w_gate": ((d, ff), d ** -0.5), "w_up": ((d, ff), d ** -0.5),
        "w_down": ((ff, d), ff ** -0.5),
    }
    return {n: _normal(ks[n], s, std) for n, (s, std) in shapes.items()}


def outer_weights(key, conf: dict) -> dict:
    """Embedding, final norm scale and (untied) head, float32."""
    m = dims(conf)
    ke, kn, kh = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    out = {
        "embed": _normal(ke, (m["vocab"], m["d"]), EMBED_STD),
        "final_norm": _normal(kn, (m["d"],), NORM_STD),
    }
    if not m["tied"]:
        out["lm_head"] = _normal(kh, (m["d"], m["vocab"]), m["d"] ** -0.5)
    return out


def program_config(conf: dict, link: dict, remat: bool = True):
    """The program's ``ModelConfig`` for a configuration file, with the
    channel of the traffic mix (``link``) at the split."""
    from repro.configs.base import LayerSpec, LinkConfig, ModelConfig

    m = dims(conf)
    lk = conf["link"]
    return ModelConfig(
        name=conf["name"], arch_type="dense", source=conf["source"],
        num_layers=m["layers"], d_model=m["d"], num_heads=m["h"],
        num_kv_heads=m["kv"], d_ff=m["ff"], vocab_size=m["vocab"],
        qkv_bias=True, act=conf["hidden_act"], gated_mlp=True,
        norm="rmsnorm", rope_theta=float(conf["rope_theta"]),
        tie_embeddings=m["tied"], unit_pattern=(LayerSpec(kind="attn"),),
        link=LinkConfig(
            split_after_units=m["split"],
            dropout_rate=float(lk["dropout_rate"]),
            loss_rate=float(link.get("loss_rate", 0.0)),
            compression="quant", quant_bits=int(lk["quant_bits"]),
            shuffle=bool(lk["shuffle"]),
            channel=link.get("channel", "iid"),
            channel_params=tuple(sorted(link.get("channel_params", {}).items())),
        ),
        dtype=conf["torch_dtype"], remat=remat,
    )


def _program_tree(conf: dict, key) -> dict:
    m = dims(conf)
    dtype = jnp.dtype(conf["torch_dtype"])
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(m["layers"], dtype=jnp.uint32))
    stacked = jax.lax.map(
        lambda k: {n: a.astype(dtype) for n, a in layer_weights(k, conf).items()},
        keys)
    outer = outer_weights(key, conf)
    lo, hi = conf["link"]["clip"]
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
        "link": {"s_min": jnp.full((m["d"],), lo, jnp.float32),
                 "s_max": jnp.full((m["d"],), hi, jnp.float32)},
    }
    if "lm_head" in outer:
        tree["lm_head"] = outer["lm_head"].astype(dtype)
    return tree


@functools.lru_cache(maxsize=4)
def _program_fn(conf_json: str):
    conf = json.loads(conf_json)
    return jax.jit(lambda k: _program_tree(conf, k))


def program_params(conf: dict, seed: int):
    """The program's parameter pytree, made on the device in one jitted
    call from the seed."""
    return _program_fn(json.dumps(conf, sort_keys=True))(weight_key(seed))


def check_layout(params, cfg) -> None:
    """The weights have the program's own pytree, shapes and dtypes."""
    from repro.models import lm

    want = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"weight layout differs from the program's: {got} != {want}")
