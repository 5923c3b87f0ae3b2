"""Mixture-of-Experts FFN with capacity-based sort/scatter dispatch.

TPU adaptation notes:
  * Dispatch is the sort-and-scatter formulation (argsort tokens by expert,
    rank-within-expert, drop beyond capacity, scatter into an (E, C, d)
    buffer) rather than the GShard (S, E, C) one-hot einsum — the one-hot
    dispatch tensor at our shapes (S=4096, E=64, C≈480) is ~250 MB/group and
    dominates HBM traffic; the scatter buffer is E*C*d ≈ tens of MB.
  * Expert weights carry the 'experts' logical dim -> sharded over the mesh
    'model' axis (64/16 = 4 or 16/16 = 1 experts per device). GSPMD turns the
    token->expert resharding into the all-to-all exchange.
  * Compute is proportional to E*C = tokens * top_k * capacity_factor, so
    HLO_FLOPs stay comparable to 6*N_active*D (checked in the roofline's
    MODEL_FLOPS ratio).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import compat

from repro.models.layers import rms_norm_defs
from repro.models.param import ParamDef


def moe_defs(cfg) -> dict:
    d = cfg.d_model
    m = cfg.moe
    dt = jnp.dtype(cfg.param_dtype)
    s = 0.02
    return {
        "norm": rms_norm_defs(d, dt),
        "router": ParamDef((d, m.n_experts), ("d_model", "experts_router"), dt, "normal", s),
        "w_gate": ParamDef((m.n_experts, d, m.d_ff_expert), ("experts", "d_model", "d_ff"), dt, "normal", s),
        "w_up": ParamDef((m.n_experts, d, m.d_ff_expert), ("experts", "d_model", "d_ff"), dt, "normal", s),
        "w_down": ParamDef((m.n_experts, m.d_ff_expert, d), ("experts", "d_ff", "d_model"), dt, "normal",
                           s / math.sqrt(2 * cfg.n_layers)),
    }


def capacity(n_tokens: int, m) -> int:
    return max(1, int(math.ceil(n_tokens * m.experts_per_token
                                * m.capacity_factor / m.n_experts)))


def moe_apply_sharded(p, x, cfg, mesh, dp_axes):
    """Expert-parallel MoE via shard_map.

    Every (pod, data) rank holds its token shard replicated across the
    'model' axis; every 'model' rank holds E/model_size experts. Each rank
    dispatches its local tokens to its local experts with a purely local
    sort/scatter (no giant one-hot einsum, no global gather — the failure
    mode of letting GSPMD partition the dispatch), computes the expert FFN,
    and the per-token combine is ONE psum over 'model' per layer, the same
    collective cost as a dense TP layer.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E, k = m.n_experts, m.experts_per_token
    msize = mesh.shape["model"]
    assert E % msize == 0, (E, msize)
    E_loc = E // msize
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    B, S, d = x.shape
    T_loc = (B // ndp) * S
    C = capacity(T_loc, m)

    def local_fn(x_loc, router_w, wg, wu, wd):
        Bl, Sl, dl = x_loc.shape
        T = Bl * Sl
        xt = x_loc.reshape(T, dl)
        logits = (xt @ router_w.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        density = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
        density_proxy = jnp.mean(probs, axis=0)
        aux = jnp.sum(density * density_proxy) * E * m.aux_loss_weight
        if dp:
            aux = jax.lax.pmean(aux, dp)

        offset = jax.lax.axis_index("model") * E_loc
        flat_ids = expert_ids.reshape(-1) - offset            # (T*k,) local ids
        flat_gate = gate_vals.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(T), k)
        in_range = (flat_ids >= 0) & (flat_ids < E_loc)
        key = jnp.where(in_range, flat_ids, E_loc)
        order = jnp.argsort(key, stable=True)
        skey = key[order]
        group_start = jnp.searchsorted(skey, jnp.arange(E_loc), side="left")
        rank = jnp.arange(T * k) - group_start[jnp.clip(skey, 0, E_loc - 1)]
        keep = (skey < E_loc) & (rank < C)
        slot_e = jnp.where(keep, skey, 0)
        slot_c = jnp.where(keep, rank, 0)
        src = flat_token[order]

        contrib = jnp.where(keep[:, None], xt[src], 0).astype(x_loc.dtype)
        buf = jnp.zeros((E_loc, C, dl), x_loc.dtype).at[slot_e, slot_c].add(contrib)

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(x_loc.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(x_loc.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * u
        y = jnp.einsum("ecf,efd->ecd", h, wd.astype(x_loc.dtype))

        gathered = y[slot_e, slot_c]
        w8 = jnp.where(keep, flat_gate[order], 0.0)[:, None].astype(x_loc.dtype)
        out = jnp.zeros((T, dl), x_loc.dtype).at[src].add(gathered * w8)
        out = jax.lax.psum(out, "model")
        return out.reshape(Bl, Sl, dl), aux

    fn = compat.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp_spec, None, None), P()))
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_apply(p, x, cfg, shard=None):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    if shard is not None:
        mesh, dp_axes = shard
        if mesh.shape.get("model", 1) > 1 and cfg.moe.n_experts % mesh.shape["model"] == 0:
            return moe_apply_sharded(p, x, cfg, mesh, dp_axes)
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    k = m.experts_per_token
    E = m.n_experts
    C = capacity(T, m)
    xt = x.reshape(T, d)

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)                   # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balancing auxiliary loss.
    density = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E * m.aux_loss_weight

    # ---- sort/scatter dispatch --------------------------------------------
    flat_expert = expert_ids.reshape(-1)                 # (T*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_expert, stable=True)        # group by expert
    sorted_expert = flat_expert[order]
    # rank of each assignment within its expert group
    pos = jnp.arange(T * k)
    group_start = jnp.searchsorted(sorted_expert, jnp.arange(E), side="left")
    rank = pos - group_start[sorted_expert]
    keep = rank < C
    slot_e = jnp.where(keep, sorted_expert, 0)
    slot_c = jnp.where(keep, rank, 0)
    src_token = flat_token[order]

    buf = jnp.zeros((E, C, d), x.dtype)
    contrib = jnp.where(keep[:, None], xt[src_token], 0).astype(x.dtype)
    buf = buf.at[slot_e, slot_c].add(contrib)            # (E, C, d)

    # ---- expert FFN (dense over E*C slots; E sharded over 'model') ---------
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))   # (E, C, d)

    # ---- combine back -------------------------------------------------------
    gathered = y[slot_e, slot_c]                          # (T*k, d)
    weighted = gathered * jnp.where(keep, flat_gate[order], 0.0)[:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[src_token].add(weighted)
    return out.reshape(B, S, d), aux
