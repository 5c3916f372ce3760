"""A made-up architecture for the tests: Qwen2's layers with no q, k or v
biases, which the program runs with ``qkv_bias=False``.

A test copies this file into a tiny checkout as
``bench/arch/MadeUpNoBiasForCausalLM.py``, beside a configuration that
names that class: an architecture enters the benchmark as files only.  It
serves; it has no ``canonical``, so no training cell takes it.
"""

import dataclasses
from pathlib import Path

from bench import flops, model

BIASES = ("bq", "bk", "bv")
base = model.load_arch(str(Path(__file__).resolve().parents[2]), "Qwen2ForCausalLM")


def program_config(conf, link, remat=True):
    return dataclasses.replace(base.program_config(conf, link, remat), qkv_bias=False)


def program_tree(conf, key):
    tree = base.program_tree(conf, key)
    for name in BIASES:
        del tree["stack"]["units"][0]["mix"][name]
    return tree


def layer_weights(key, conf, kind):
    return {n: a for n, a in base.layer_weights(key, conf, kind).items() if n not in BIASES}


outer_weights = base.outer_weights


def layer_forward(x, w, conf, prec, kind):
    return base.layer_forward(x, dict(w, **{name: 0.0 for name in BIASES}), conf, prec, kind)


def _biases(conf):
    """Bias parameters a Qwen2 layer has and this one has not."""
    m = base.dims(conf)
    return (m["h"] + 2 * m["kv"]) * m["hd"]


train_step_flops = base.train_step_flops
decode_attention = base.decode_attention


def param_count(conf):
    return base.param_count(conf) - conf["num_hidden_layers"] * _biases(conf)


def decode_steps(conf, steps, counters):
    f, b = base.decode_steps(conf, steps, counters)
    return f, b - steps * conf["num_hidden_layers"] * _biases(conf) * flops.itemsize(conf)
