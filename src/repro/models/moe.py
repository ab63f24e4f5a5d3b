"""Mixture-of-Experts FFN with sort-based capacity dispatch (GShard/Switch
lineage, dropless-ish): tokens are routed top-k, assignments sorted by
expert, packed into a static (E, C, d) buffer (EP-shardable over the
``model`` mesh axis), processed with per-expert SwiGLU GEMMs, and combined
with gate-weighted scatter-add. Tokens beyond capacity are dropped with
zero weight (capacity_factor controls the drop rate).

FLOP accounting: expert GEMMs cost E*C*d*ff*3*2 = T*k*cf*d*ff*6 — i.e.
active-parameter FLOPs x capacity factor, matching the 6*N_active*D
roofline convention for MoE.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import normal_init, swiglu, swiglu_init


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _expert_swiglu(gathered, wg, wu, wd):
    g = jnp.einsum("ecd,edf->ecf", gathered, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", gathered, wu,
                   preferred_element_type=jnp.float32).astype(gathered.dtype)
    h = jax.nn.silu(g).astype(gathered.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd,
                      preferred_element_type=jnp.float32)


def capacity(n_tokens: int, top_k: int, n_experts: int, factor: float,
             multiple: int = 8) -> int:
    c = int(n_tokens * top_k * factor / n_experts)
    return max(_round_up(max(c, 1), multiple), multiple)


def moe_init(key, cfg, dtype):
    ke, kr, ks = jax.random.split(key, 3)
    d, ff, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    k1, k2, k3 = jax.random.split(ke, 3)
    params = {
        "router": normal_init(kr, (d, E), jnp.float32, stddev=0.02),
        "w_gate": normal_init(k1, (E, d, ff), dtype),
        "w_up": normal_init(k2, (E, d, ff), dtype),
        "w_down": normal_init(k3, (E, ff, d), dtype),
    }
    if cfg.n_shared_experts:
        params["shared"] = swiglu_init(ks, d, ff * cfg.n_shared_experts, dtype)
    return params


def moe_ffn(params, x, cfg, compute_dtype, mi=None):
    """x: (B, S, d) -> (B, S, d), plus router aux loss (load balancing).
    mi: optional MeshInfo — EP sharding constraints on the dispatch
    buffers (experts over ``model``)."""
    if cfg.moe_impl == "shard_map" and mi is not None and mi.active:
        return moe_ffn_shard_map(params, x, cfg, compute_dtype, mi)
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    tokens = x.reshape(T, d)
    if mi is not None and mi.active:
        from jax.sharding import PartitionSpec as P
        c_exp = lambda t: mi.constraint(
            t, P(mi.model_axis, *([None] * (t.ndim - 1))))
    else:
        c_exp = lambda t: t

    # --- routing (fp32 for numerics) ---
    logits = tokens.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balancing aux loss.
    density = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (T * k)
    mean_prob = probs.mean(axis=0)
    aux_loss = cfg.router_aux_loss * E * jnp.sum(density * mean_prob)

    # --- sort-based dispatch ---
    C = capacity(T, k, E, cfg.capacity_factor)
    flat_e = expert_idx.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[order]
    token_of = order // k  # originating token per sorted assignment
    # position of each assignment within its expert's bucket
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))  # (E,)
    pos_in_e = jnp.arange(T * k) - starts[sorted_e]
    keep = pos_in_e < C  # capacity drop mask
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # E*C = trash slot

    gathered = jnp.zeros((E * C + 1, d), compute_dtype)
    gathered = gathered.at[slot].set(tokens.astype(compute_dtype)[token_of])
    gathered = c_exp(gathered[:-1].reshape(E, C, d))

    # --- per-expert SwiGLU (EP: experts stay sharded over `model`; the
    # FSDP d-dim of each expert weight is explicitly gathered in bf16) ---
    if mi is not None and mi.active:
        wg = mi.wgather(params["w_gate"].astype(compute_dtype), 0)
        wu = mi.wgather(params["w_up"].astype(compute_dtype), 0)
        wd = mi.wgather(params["w_down"].astype(compute_dtype), 0)
    else:
        wg = params["w_gate"].astype(compute_dtype)
        wu = params["w_up"].astype(compute_dtype)
        wd = params["w_down"].astype(compute_dtype)
    g = c_exp(jnp.einsum("ecd,edf->ecf", gathered, wg,
                         preferred_element_type=jnp.float32))
    u = c_exp(jnp.einsum("ecd,edf->ecf", gathered, wu,
                         preferred_element_type=jnp.float32)
              .astype(compute_dtype))
    h = c_exp(jax.nn.silu(g).astype(compute_dtype) * u)
    y = c_exp(jnp.einsum("ecf,efd->ecd", h, wd,
                         preferred_element_type=jnp.float32))
    y = y.reshape(E * C, d)

    # --- gate-weighted combine (scatter-add back to tokens) ---
    sorted_gates = gate_vals.reshape(-1)[order] * keep
    contrib = y[jnp.where(keep, sorted_e * C + pos_in_e, 0)] * sorted_gates[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[token_of].add(contrib)

    if cfg.n_shared_experts:
        shared_c = None
        wgt = None
        if mi is not None and mi.active:
            from jax.sharding import PartitionSpec as P2
            # tokens dim stays dp-sharded! P(None, model) would REPLICATE
            # the (B*S, ff) hidden over `data` — measured as 21.5 GB f32
            # all-gathers of the global token matrix (§Perf iteration 6).
            shared_c = lambda t: mi.constraint(t, P2(mi.dp(), mi.model_axis))
            wgt = mi.wgather
        out = out + swiglu(params["shared"], tokens, compute_dtype,
                           constrain=shared_c,
                           wgather=wgt).astype(jnp.float32)

    return out.reshape(B, S, d).astype(x.dtype), aux_loss


def _local_dispatch_ffn(tokens, router_w, wg, wu, wd, *, cfg, compute_dtype,
                        e_lo, n_local):
    """Per-device MoE over the device's local expert slice [e_lo, e_lo+n).

    tokens: (T, d) — the full row-replicated token set. Because the batch
    is sharded over `data` only, every device along `model` already holds
    the same tokens: dispatch is a LOCAL gather (no all-to-all), and the
    combine is one psum of the (T, d) output over `model` — the Megatron
    all-reduce the layer pays anyway. Returns (partial_out, aux_partial).
    """
    T, d = tokens.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = tokens.astype(jnp.float32) @ router_w  # (T, E), replicated work
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    density = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) \
        / (T * k)
    aux = cfg.router_aux_loss * E * jnp.sum(density * probs.mean(axis=0))

    # keep only assignments owned by this device's experts
    owned = (expert_idx >= e_lo) & (expert_idx < e_lo + n_local)
    flat_e = jnp.where(owned, expert_idx - e_lo, n_local).reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    token_of = order // k
    C = capacity(T, k, E, cfg.capacity_factor)
    starts = jnp.searchsorted(sorted_e, jnp.arange(n_local))
    pos_in_e = jnp.arange(T * k) - starts[jnp.clip(sorted_e, 0, n_local - 1)]
    keep = (pos_in_e < C) & (sorted_e < n_local)
    slot = jnp.where(keep, sorted_e * C + pos_in_e, n_local * C)

    gathered = jnp.zeros((n_local * C + 1, d), compute_dtype)
    gathered = gathered.at[slot].set(tokens.astype(compute_dtype)[token_of])
    gathered = gathered[:-1].reshape(n_local, C, d)
    y = _expert_swiglu(gathered, wg, wu, wd).reshape(n_local * C, d)

    sorted_gates = gate_vals.reshape(-1)[order] * keep
    contrib = y[jnp.where(keep, slot, 0)] * sorted_gates[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[token_of].add(contrib)
    return out, aux / lax.psum(1, "model")  # aux replicated -> de-duplicate


def moe_ffn_shard_map(params, x, cfg, compute_dtype, mi):
    """EP via shard_map: local dispatch, psum combine (§Perf iteration 4)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    B, S, d = x.shape
    dp = mi.dp()
    E = cfg.n_experts

    n_model = mi.mesh.shape[mi.model_axis]  # static (lax.axis_size is not
    # available on older jax, and n_local must be static anyway)

    def fn(xs, router_w, wg, wu, wd):
        midx = lax.axis_index(mi.model_axis)
        n_local = E // n_model
        tokens = xs.reshape(-1, d)
        out, aux = _local_dispatch_ffn(
            tokens, router_w, wg.astype(compute_dtype),
            wu.astype(compute_dtype), wd.astype(compute_dtype),
            cfg=cfg, compute_dtype=compute_dtype,
            e_lo=midx * n_local, n_local=n_local)
        out = lax.psum(out, mi.model_axis)  # the combine (one all-reduce)
        aux = lax.psum(aux, mi.model_axis)
        return out.reshape(xs.shape).astype(xs.dtype), aux[None]

    # cast to bf16 BEFORE the shard_map boundary: the expert weights'
    # FSDP dim is all-gathered over `data` on entry, and gathering fp32
    # doubles that traffic (llama4: 81s -> measured below, §Perf it. 6)
    wg_c = params["w_gate"].astype(compute_dtype)
    wu_c = params["w_up"].astype(compute_dtype)
    wd_c = params["w_down"].astype(compute_dtype)
    out, aux = shard_map(
        fn, mesh=mi.mesh,
        in_specs=(P(dp, None, None), P(), P(mi.model_axis, None, None),
                  P(mi.model_axis, None, None), P(mi.model_axis, None, None)),
        out_specs=(P(dp, None, None), P(None)),
        check_vma=False)(
        x, params["router"], wg_c, wu_c, wd_c)
    aux_loss = aux[0]

    if cfg.n_shared_experts:
        from jax.sharding import PartitionSpec as P2
        tokens = x.reshape(-1, d)
        out2 = swiglu(params["shared"], tokens, compute_dtype,
                      constrain=lambda t: mi.constraint(
                          t, P2(mi.dp(), mi.model_axis)),
                      wgather=mi.wgather).reshape(x.shape)
        out = out + out2.astype(out.dtype)
    return out, aux_loss
