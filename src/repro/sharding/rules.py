"""GSPMD partition rules for parameters, optimizer state, activations and
decode caches over the production mesh.

Axis roles:
  "model"        — tensor/expert parallelism: the fused heads*head_dim or
                   d_ff feature dim, or the MoE expert dim.  The fused
                   (heads*head_dim) layout shards evenly even when the head
                   count doesn't divide the axis (arctic 56H, musicgen 24H,
                   xlstm 4H).
  "data" (+"pod")— batch parallelism, plus FSDP/ZeRO: the d_model dim of
                   every large parameter is sharded over data so parameters,
                   gradients and Adam state all scale down with the data
                   axis.
Every rule is divisibility-guarded: a dim that doesn't divide the axis size
falls back to replication for that dim (never fails to lower).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import LayerSpec, ModelConfig, ShapeConfig
from repro.models import attention as attention_lib


def data_axes(mesh: Mesh):
    """('pod','data') on multi-pod meshes, ('data',) on single-pod."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _guard(mesh: Mesh, shape, spec: Sequence) -> P:
    """Drop any spec entry whose axis size doesn't divide the dim."""
    out = []
    for dim, axes in zip(shape, spec):
        if axes is None or dim % _axis_size(mesh, axes) != 0:
            out.append(None)
        else:
            out.append(axes)
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# name -> spec template builder(DATA) for the *unstacked* (per-layer) shape.
def _param_template(name: str, ndim: int, data):
    two_d_in = (data, "model")      # (d_model, features)
    two_d_out = ("model", data)     # (features, d_model)
    table = {
        "embed": ("model", data),
        "lm_head": two_d_in,
        "wq": two_d_in, "wk": two_d_in, "wv": two_d_in,
        "wz": two_d_in, "wi": two_d_in, "wf": two_d_in, "wo": two_d_in,
        "in_proj": two_d_in, "proj": two_d_in,
        "w_out": two_d_out, "out_proj": two_d_out,
        "bq": ("model",), "bk": ("model",), "bv": ("model",),
        "f_bias": ("model",), "conv_b": ("model",), "dt_bias": ("model",),
        "D": ("model",),
        "router": (data, None),
        "conv_w": (None, "model"),
        "x_proj": ("model", None),
        "dt_proj": (None, "model"),
        "A_log": ("model", None),
        "rz": (None, None, None), "ri": (None, None, None),
        "rf": (None, None, None), "ro": (None, None, None),
    }
    if name in ("w_up", "w_gate"):
        return ("model", data, None) if ndim == 3 else two_d_in
    if name == "w_down":
        return ("model", None, data) if ndim == 3 else two_d_out
    return table.get(name)  # None -> replicate


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            names.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            names.append(f"[{k.idx}]")
        else:
            names.append(str(k))
    return tuple(names)


def param_pspecs(params_shapes: Any, mesh: Mesh, fsdp="on") -> Any:
    """Map a params pytree (of arrays or ShapeDtypeStructs) to PartitionSpecs.

    fsdp modes (EXPERIMENTS.md §Perf):
      "on" / True    — baseline: d_model dim of every large parameter is
                       sharded over 'data' (ZeRO-3-style).  Measured cost:
                       GSPMD resolves the data-sharded contraction dim with
                       full-batch activation all-reduces over 'data'.
      "off" / False  — replicate over 'data': no FSDP all-reduces, maximal
                       parameter memory (fine for small models).
      "expert"       — non-expert params replicated over 'data'; MoE expert
                       tensors shard the *per-expert FFN dim* over 'data'
                       (w_up/w_gate (E,d,f): E@model + f@data; w_down
                       (E,f,d): E@model + f@data).  Only the w_down
                       contraction pays a (E/m, C, d) all-reduce — ~10x
                       smaller than the baseline's full-batch ARs — while
                       expert memory still scales down with both axes.
    """
    if fsdp is True:
        fsdp = "on"
    if fsdp is False:
        fsdp = "off"
    data = data_axes(mesh)
    data = data if len(data) > 1 else (data[0] if data else None)
    if fsdp == "off":
        data = None

    def assign(path, leaf):
        names = _path_names(path)
        name = names[-1]
        stacked = "units" in names  # leading U scan dim
        shape = leaf.shape
        base_shape = shape[1:] if stacked else shape
        if fsdp == "expert":
            if name in ("w_up", "w_gate") and len(base_shape) == 3:
                tpl = ("model", None, data)
            elif name == "w_down" and len(base_shape) == 3:
                tpl = ("model", data, None)
            else:
                tpl = _param_template(name, len(base_shape), None)
        else:
            tpl = _param_template(name, len(base_shape), data)
        if tpl is None:
            return P()  # replicate (norms, link scales, small vectors)
        tpl = tuple(tpl)[: len(base_shape)]
        tpl = tpl + (None,) * (len(base_shape) - len(tpl))
        spec = ((None,) if stacked else ()) + tpl
        return _guard(mesh, shape, spec)

    return jax.tree_util.tree_map_with_path(assign, params_shapes)


def opt_state_pspecs(opt_shapes: Any, params_specs: Any, mesh: Mesh) -> Any:
    """AdamState(step, mu, nu): mu/nu inherit parameter specs."""
    from repro.optim.adam import AdamState

    return AdamState(step=P(), mu=params_specs, nu=params_specs)


# ---------------------------------------------------------------------------
# Activation / cache rules
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh, batch: int) -> Optional[Tuple[str, ...]]:
    """Largest prefix of ('pod','data') that divides the batch."""
    axes = data_axes(mesh)
    if axes and batch % _axis_size(mesh, axes) == 0:
        return axes
    if len(axes) > 1 and batch % _axis_size(mesh, axes[-1:]) == 0:
        return axes[-1:]
    return None


def token_pspec(mesh: Mesh, batch: int) -> P:
    return P(batch_spec(mesh, batch), None)


def _kv_head_axes(mesh: Mesh, kv_heads: int, head_dim: int):
    """(kv_axis, hd_axis): prefer sharding kv heads over 'model', fall back
    to head_dim, else replicate."""
    m = mesh.shape["model"]
    if kv_heads % m == 0:
        return "model", None
    if head_dim % m == 0:
        return None, "model"
    return None, None


def cache_pspecs(cfg: ModelConfig, shape_cfg: ShapeConfig, mesh: Mesh) -> Any:
    """PartitionSpec tree mirroring models.cache.init_cache structure.

    Normal decode: batch over data, kv/head_dim over model.
    long-context (batch not shardable): KV seq dim over data
    (context-parallel decode); recurrent states shard features over model.
    """
    b = shape_cfg.global_batch
    bs = batch_spec(mesh, b)
    seq_ax = None
    if bs is None:
        # batch unshardable (long_500k): context-parallel the KV seq dim
        seq_ax = data_axes(mesh) or None
    kv_ax, hd_ax = _kv_head_axes(mesh, cfg.num_kv_heads, cfg.resolved_head_dim)
    m = mesh.shape["model"]

    def attn_spec(spec: LayerSpec, stacked: bool):
        length = attention_lib.cache_len(spec, shape_cfg.seq_len)
        s_ax = seq_ax if (seq_ax and length % _axis_size(mesh, seq_ax) == 0) else None
        # K/V rows are KV * hd lanes: sharding them over 'model' splits
        # whole heads (kv_ax), or else an equal run of each row's lanes.
        base = (bs, s_ax, kv_ax or hd_ax)
        kv = P(*(((None,) if stacked else ()) + base))
        out = {"k": kv, "v": kv}
        if cfg.kv_cache_dtype == "int8":
            sc = P(*(((None,) if stacked else ()) + (bs, s_ax, kv_ax)))
            out["k_scale"] = sc
            out["v_scale"] = sc
        return out

    def feat_ax(dim):
        return "model" if dim % m == 0 else None

    def mamba_spec(stacked: bool):
        di = cfg.mamba_d_inner
        pre = (None,) if stacked else ()
        return {
            "conv": P(*(pre + (bs, None, feat_ax(di)))),
            "ssm": P(*(pre + (bs, feat_ax(di), None))),
        }

    def mlstm_spec(stacked: bool):
        dh = cfg.xlstm_head_dim
        pre = (None,) if stacked else ()
        return {
            "c": P(*(pre + (bs, None, feat_ax(dh), None))),
            "n": P(*(pre + (bs, None, feat_ax(dh)))),
            "m": P(*(pre + (bs, None))),
        }

    def slstm_spec(stacked: bool):
        dh = cfg.xlstm_head_dim
        pre = (None,) if stacked else ()
        v = P(*(pre + (bs, None, feat_ax(dh))))
        return {"c": v, "n": v, "m": v, "h": v}

    def layer_spec(spec: LayerSpec, stacked: bool):
        if spec.kind == "attn":
            return attn_spec(spec, stacked)
        if spec.kind == "mamba":
            return mamba_spec(stacked)
        if spec.kind == "mlstm":
            return mlstm_spec(stacked)
        if spec.kind == "slstm":
            return slstm_spec(stacked)
        raise ValueError(spec.kind)

    return {
        "prologue": [layer_spec(s, stacked=False) for s in cfg.prologue],
        "units": [layer_spec(s, stacked=True) for s in cfg.unit_pattern],
    }


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def pool_shard_devices(mesh: Mesh) -> list:
    """Device list the sharded serving router builds per-shard slot pools
    over: one shard per data-axis step of ``mesh``, in data-major order.

    The slot axis is a *data* axis (independent batch-1 requests), so the
    router shards it over the mesh's data-like axes only; a ``model`` axis
    wider than 1 would mean tensor-parallel shards, which the per-shard
    ``Compiled``-executable design does not cover yet — refuse loudly
    instead of silently serving from a mis-shaped pool.  Each returned
    device hosts one full ``ContinuousEngine`` slot/block pool (the
    cache layout per shard is exactly the single-device layout that
    :func:`cache_pspecs` replicates along these axes).
    """
    if "model" in mesh.axis_names and mesh.shape["model"] != 1:
        raise ValueError(
            f"sharded serving shards the slot (data) axis only; mesh has "
            f"model axis of size {mesh.shape['model']} — build the host "
            "mesh with model_axis=1 for the serving router"
        )
    return list(mesh.devices.flat)
