"""A configuration file as the program runs it, and its seeded weights.

The configuration files under ``bench/configs`` use the keys of the
model's published ``config.json``.  What depends on the architecture is in
one module per architecture, ``bench/arch/<architectures[0]>.py`` under
the root the configuration was read from (``read_conf``, ``arch``), found
by name as the metric readers are; a configuration of a new architecture
is then new files only.  Such a module provides:

* ``program_config(conf, link, remat)``: the program's ``ModelConfig``
  (the system under test), with ``link_config(conf, link)`` at the split;
* ``program_tree(conf, key)``: the program's parameter pytree, less the
  link's clip range, from the weight key; layer ``i`` drawn from
  ``layer_key(key, i)``, the rest from ``outer_key(key)``;
* ``canonical(tree)``: a tree shaped like the program's parameters under
  the reference's names (training's comparison);
* optionally ``layer_kind(conf, i)``: a hashable that tells the kinds of
  layer apart by index (a dense layer 0 and expert layers after it, say);
  without it every layer is of one kind, ``0``;
* ``layer_weights(key, conf, kind)`` and ``outer_weights(key, conf)``: the
  reference's float32 weights of a layer of that kind and of everything
  outside the layers, from those same keys;
* ``layer_forward(x, w, conf, prec, kind)``: one reference layer over
  full causal sequences, written with ``bench/reference.py``'s ``mm``,
  ``ein``, ``rmsnorm`` and ``rope`` (so that the float8 control covers
  it), and optionally ``head_logits`` in place of the reference's;
* optionally, for a layer with routed experts, two hooks that let the
  check tell a router near-tie from a wrong program
  (``reference._TieSearch``, used where the cell's limits file gives
  ``served_gap.router_tie``): ``route_margins(x, w, conf, kind)``, for each
  position of the layer's input (B, S, d), the selection-score gap
  between the last chosen expert and the first unchosen one (B, S), or
  ``None`` for a layer without a router; and a ``flips`` argument to
  ``layer_forward`` (a (B, S) boolean mask, or ``None``): at the
  positions it marks the layer takes the first unchosen expert in place
  of the last chosen one, its combine weights formed as the published
  router forms them;
* ``param_count``, ``train_step_flops``, ``decode_steps`` and
  ``decode_attention``: the operations and bytes that the benchmark
  reads through ``bench/flops.py`` (which lists what else it may give).

The reference compiles one program per kind of layer, not per layer.

Shared here: the keys from the seed, the spread of the random weights,
the COMtune link's configuration and clip range, the lookup, and the check
that the weights have the program's own layout.  Weights are drawn layer
by layer, so the plain reference can draw any one layer again without the
program and without holding the whole model.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp

OUTER = 1 << 20          # the fold-in of everything outside the layers
# Spread of the random weights.  Matrices use 1/sqrt(fan_in); biases and
# norm scales are small but non-zero so that a path that drops them shows.
BIAS_STD, NORM_STD, EMBED_STD = 0.1, 0.1, 0.02


def load_module(path: Path, prefix: str):
    """The Python file at ``path`` as a module of its own."""
    name = prefix + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def load_arch(root: str, name: str):
    path = Path(root) / "bench" / "arch" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"architecture {name!r}: no module at {path}")
    return load_module(path, "bench_arch_")


def arch(conf: dict):
    """The module of the configuration's architecture, under the root it
    was read from (``bench_root``)."""
    return load_arch(conf["bench_root"], conf["architectures"][0])


def read_conf(root: Path, file: str) -> dict:
    """The configuration file ``file`` under ``root``, with the root
    recorded as ``bench_root``.  An unknown architecture stops here,
    before any weights are drawn."""
    conf = dict(json.loads((Path(root) / file).read_text()), bench_root=str(root))
    arch(conf)
    return conf


def layer_kind(conf: dict, i: int):
    kind = getattr(arch(conf), "layer_kind", None)
    return 0 if kind is None else kind(conf, i)


def dims(conf: dict) -> dict:
    """Sizes that every architecture has."""
    return {
        "d": conf["hidden_size"], "vocab": conf["vocab_size"],
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


def layer_key(key, i) -> jax.Array:
    return jax.random.fold_in(key, i)


def outer_key(key) -> jax.Array:
    return jax.random.fold_in(key, OUTER)


def normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def link_config(conf: dict, link: dict):
    """The program's ``LinkConfig``: the configuration's link with the
    channel of the traffic mix (``link``)."""
    from repro.configs.base import LinkConfig

    lk = conf["link"]
    return LinkConfig(
        split_after_units=dims(conf)["split"],
        dropout_rate=float(lk["dropout_rate"]),
        loss_rate=float(link.get("loss_rate", 0.0)),
        compression="quant", quant_bits=int(lk["quant_bits"]),
        shuffle=bool(lk["shuffle"]),
        channel=link.get("channel", "iid"),
        channel_params=tuple(sorted(link.get("channel_params", {}).items())),
    )


def program_config(conf: dict, link: dict, remat: bool = True):
    """The program's ``ModelConfig`` for a configuration file, with the
    channel of the traffic mix (``link``) at the split."""
    return arch(conf).program_config(conf, link, remat)


def _program_tree(conf: dict, key) -> dict:
    lo, hi = conf["link"]["clip"]
    d = dims(conf)["d"]
    return dict(arch(conf).program_tree(conf, key),
                link={"s_min": jnp.full((d,), lo, jnp.float32),
                      "s_max": jnp.full((d,), hi, jnp.float32)})


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
