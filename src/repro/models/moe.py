"""Top-k Mixture-of-Experts FFN with sort-based capacity dispatch.

Production formulation (not the dense all-experts trick):

1. router logits -> top-k experts per token, renormalized softmax gates;
2. the (tokens × k) assignments are sorted by expert id and each expert
   takes its first ``capacity`` tokens (position-in-expert via a stable
   sort + per-expert cumulative count) — overflow tokens are dropped,
   exactly like capacity-factor routing in Switch/GShard/Mesh;
3. tokens are gathered into an (E, C, d) buffer, experts run as a single
   batched einsum (E-sharded over the "model" mesh axis = expert
   parallelism; GSPMD inserts the all-to-alls), results scatter-add back
   with gate weights.

Variants required by the assigned archs:
* shared experts (Kimi-K2): dense FFN(s) of the expert width applied to all
  tokens, added to the routed output;
* dense residual (Arctic): a full dense FFN in parallel with the MoE.

Load-balance auxiliary loss (Switch-style): E · Σ_e f_e · P_e.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Params, activation, dense_init, split_keys
from repro.models.mlp import init_mlp, mlp_forward

def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_dff or cfg.d_ff
    ks = split_keys(key, 6)
    p = {
        "router": dense_init(ks[0], (d, e), dtype, scale=0.1),
        "w_up": dense_init(ks[1], (e, d, f), dtype),
        "w_gate": dense_init(ks[2], (e, d, f), dtype),
        "w_down": dense_init(ks[3], (e, f, d), dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(
            ks[4], d, f * cfg.num_shared_experts, cfg.gated_mlp, dtype
        )
    if cfg.dense_residual_dff:
        p["dense_residual"] = init_mlp(
            ks[5], d, cfg.dense_residual_dff, cfg.gated_mlp, dtype
        )
    return p


def _capacity(num_tokens: int, cfg: ModelConfig) -> int:
    cap = int(num_tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(cfg.top_k, cap)


def moe_forward(
    p: Params, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    Dispatches to the shard_map expert-parallel formulation when a mesh
    context is active (launch/steps.py) and the expert count divides the
    'model' axis; otherwise runs the single-device/GSPMD formulation below.
    """
    from repro.sharding import ctx as shard_ctx

    mesh = shard_ctx.shard_map_mesh()
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and cfg.num_experts % mesh.shape["model"] == 0
    ):
        return moe_forward_shard_map(p, x, cfg, mesh)
    return moe_forward_dense(p, x, cfg)


def moe_forward_dense(
    p: Params, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Single-program formulation (scatter/gather dispatch).  Under GSPMD
    the computed-index scatter partitions catastrophically (measured: ~60 GB
    full-payload all-reduces per MoE layer on arctic x train_4k — see
    EXPERIMENTS.md §Perf hillclimb 1); production meshes use
    moe_forward_shard_map instead."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    # --- routing ---
    logits = (xt @ p["router"]).astype(jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # --- load-balance aux (Switch): E * sum_e f_e * P_e ---
    one_hot_top1 = jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32)
    f_e = one_hot_top1.mean(axis=0)
    p_e = probs.mean(axis=0)
    aux = jnp.float32(e) * jnp.sum(f_e * p_e)

    # --- capacity dispatch via stable sort ---
    cap = _capacity(t, cfg)
    flat_expert = expert_ids.reshape(-1)                     # (T*k,)
    flat_gate = gate_vals.reshape(-1).astype(x.dtype)
    flat_token = jnp.repeat(jnp.arange(t), k)                # source token ids

    order = jnp.argsort(flat_expert, stable=True)            # group by expert
    sorted_expert = flat_expert[order]
    # position within the expert's group
    pos_in_expert = jnp.arange(t * k) - jnp.searchsorted(
        sorted_expert, sorted_expert, side="left"
    )
    keep = pos_in_expert < cap
    slot = sorted_expert * cap + jnp.where(keep, pos_in_expert, 0)
    slot = jnp.where(keep, slot, e * cap)                    # dropped -> scratch

    # gather tokens into (E*C+1, d) buffer (last row = scratch for drops)
    src_tok = flat_token[order]
    buffer = jnp.zeros((e * cap + 1, d), x.dtype)
    buffer = buffer.at[slot].set(
        jnp.where(keep[:, None], xt[src_tok], 0.0), mode="drop"
    )
    expert_in = buffer[: e * cap].reshape(e, cap, d)

    # --- expert compute (E-sharded einsums) ---
    act = activation(cfg.act)
    h = act(jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", expert_in, p["w_up"]
    )
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])   # (E, C, d)

    # --- combine back with gates ---
    out_flat = expert_out.reshape(e * cap, d)
    gathered = jnp.where(
        keep[:, None], out_flat[jnp.minimum(slot, e * cap - 1)], 0.0
    )
    weighted = gathered * flat_gate[order][:, None]
    out = jnp.zeros((t, d), x.dtype).at[src_tok].add(weighted)

    # --- dense side paths ---
    if "shared" in p:
        out = out + mlp_forward(p["shared"], xt, cfg.act, cfg.gated_mlp)
    if "dense_residual" in p:
        out = out + mlp_forward(p["dense_residual"], xt, cfg.act, cfg.gated_mlp)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map formulation (production path)
# ---------------------------------------------------------------------------

def moe_forward_shard_map(
    p: Params, x: jax.Array, cfg: ModelConfig, mesh
) -> Tuple[jax.Array, jax.Array]:
    """Explicit expert parallelism: tokens are batch-sharded over the data
    axes and replicated over 'model'; each model rank routes its local
    tokens to the E/m experts it OWNS (dispatch is a purely local
    sort+scatter), runs them, and the per-rank partial outputs are combined
    with ONE psum over 'model' per layer (~|tokens|*d bytes) instead of
    GSPMD's full-payload dispatch all-reduces.  Expert weights arrive via
    shard_map's resharding = the FSDP-style weight gather."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import ctx as shard_ctx

    data_axes, model_ax = shard_ctx.mesh_axes(mesh)
    b = x.shape[0]
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    batch_axes = data_axes if (data_axes and b % n_data == 0) else ()
    e = cfg.num_experts
    m = mesh.shape[model_ax]
    e_loc = e // m

    # Routed-expert tensors enter the shard_map; shared-expert / dense
    # residual paths stay outside as ordinary GSPMD matmuls (they were never
    # the problem and keeping them out avoids gathering their weights).
    p_routed = {k: p[k] for k in ("router", "w_up", "w_gate", "w_down")}
    p_specs = {
        "router": P(),
        "w_up": P(model_ax, None, None),
        "w_gate": P(model_ax, None, None),
        "w_down": P(model_ax, None, None),
    }
    x_spec = P(batch_axes if batch_axes else None, None, None)

    def local_fn(p_loc, x_loc):
        bl, sl, d = x_loc.shape
        t = bl * sl
        xt = x_loc.reshape(t, d)
        k = cfg.top_k
        rank = jax.lax.axis_index(model_ax)
        first = rank * e_loc

        logits = (xt @ p_loc["router"]).astype(jnp.float32)      # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9
        )

        # aux loss from GLOBAL statistics (pmean over the data axes).
        one_hot_top1 = jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32)
        f_e = one_hot_top1.mean(axis=0)
        p_e = probs.mean(axis=0)
        for a in data_axes:
            f_e = jax.lax.pmean(f_e, a)
            p_e = jax.lax.pmean(p_e, a)
        aux = jnp.float32(e) * jnp.sum(f_e * p_e)

        # ---- local dispatch to OWNED experts only ----
        cap = _capacity(t, cfg)
        flat_expert = expert_ids.reshape(-1)
        flat_gate = gate_vals.reshape(-1).astype(x_loc.dtype)
        flat_token = jnp.repeat(jnp.arange(t), k)
        owned = (flat_expert >= first) & (flat_expert < first + e_loc)
        local_eid = jnp.where(owned, flat_expert - first, e_loc)   # e_loc = trash

        order = jnp.argsort(local_eid, stable=True)
        sorted_eid = local_eid[order]
        pos_in_expert = jnp.arange(t * k) - jnp.searchsorted(
            sorted_eid, sorted_eid, side="left"
        )
        keep = (sorted_eid < e_loc) & (pos_in_expert < cap)
        slot = jnp.where(keep, sorted_eid * cap + pos_in_expert, e_loc * cap)

        src_tok = flat_token[order]
        buffer = jnp.zeros((e_loc * cap + 1, d), x_loc.dtype)
        buffer = buffer.at[slot].set(
            jnp.where(keep[:, None], xt[src_tok], 0.0), mode="drop"
        )
        expert_in = buffer[: e_loc * cap].reshape(e_loc, cap, d)

        act = activation(cfg.act)
        h = act(
            jnp.einsum("ecd,edf->ecf", expert_in, p_loc["w_gate"])
        ) * jnp.einsum("ecd,edf->ecf", expert_in, p_loc["w_up"])
        expert_out = jnp.einsum("ecf,efd->ecd", h, p_loc["w_down"])

        out_flat = expert_out.reshape(e_loc * cap, d)
        gathered = jnp.where(
            keep[:, None], out_flat[jnp.minimum(slot, e_loc * cap - 1)], 0.0
        )
        weighted = gathered * flat_gate[order][:, None]
        out = jnp.zeros((t, d), x_loc.dtype).at[src_tok].add(weighted)

        out = jax.lax.psum(out, model_ax)
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p_routed, x)

    # dense side paths (plain GSPMD tensor parallelism)
    bsz, sl, d = x.shape
    xt = x.reshape(bsz * sl, d)
    if "shared" in p:
        out = out + mlp_forward(p["shared"], xt, cfg.act, cfg.gated_mlp).reshape(
            bsz, sl, d
        )
    if "dense_residual" in p:
        out = out + mlp_forward(
            p["dense_residual"], xt, cfg.act, cfg.gated_mlp
        ).reshape(bsz, sl, d)
    return out, aux
