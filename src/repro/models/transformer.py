"""Decoder-only LM backbone covering all five assigned transformer archs.

Distribution strategy (see DESIGN.md §4):
  * projections / FFN / MoE: Megatron-style TP over ``model`` + FSDP over
    ``data`` (weights), batch over ``pod``x``data`` (activations);
  * train/prefill attention: context parallelism — q sequence-sharded over
    ``model`` inside a shard_map region, KV replicated within it (exact for
    causal/windowed attention, uniformly balanced because the blockwise
    online-softmax scans all KV blocks with masking);
  * decode: KV cache sequence-sharded over ``model``; plain attention whose
    softmax/contraction reductions GSPMD lowers to split-K all-reduces
    (FlashDecoding-on-GSPMD);
  * layers are stacked and scanned (compact HLO, one traced layer body).

Model features, switched per config: GQA, RoPE (partial), qk-norm (qwen3),
attn/final logit softcap + local/global alternation + sandwich norms
(gemma2), MoE top-k with optional shared expert (moonshot/llama4), tied or
untied LM head, early-fusion patch-embedding stub (llama4).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import layers as L
from repro.models.moe import moe_ffn, moe_init


# --------------------------------------------------------------------------
# mesh plumbing
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshInfo:
    """How the model maps onto the device mesh. ``None`` mesh = single host
    (smoke tests): shard_map regions are skipped and plain ops used."""

    mesh: Any = None
    dp_axes: tuple = ("data",)  # batch axes ("pod","data") on the multi-pod mesh
    model_axis: str = "model"

    @property
    def active(self) -> bool:
        return self.mesh is not None

    def dp(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    def constraint(self, x, spec):
        if not self.active:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def wgather(self, w, tp_dim: int | None):
        """Explicit FSDP weight-gather: cast first (bf16 on the wire), then
        constrain to TP-only sharding. Without this GSPMD keeps the FSDP
        dim sharded and partial-sums activations over ``data`` instead —
        measured 13.4 GB f32 all-reduces per FFN vs a 32 MB bf16 weight
        gather (EXPERIMENTS.md §Perf iteration 2). The constraint's
        transpose reduce-scatters the weight grads back: exactly FSDP."""
        if not self.active:
            return w
        spec = [None] * w.ndim
        if tp_dim is not None:
            spec[tp_dim] = self.model_axis
        return lax.with_sharding_constraint(
            w, NamedSharding(self.mesh, P(*spec)))


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _layer_init(key, cfg, dtype):
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    p = {
        "ln1": L.rmsnorm_init(d, jnp.float32),
        "ln2": L.rmsnorm_init(d, jnp.float32),
        "wq": L.normal_init(keys[0], (d, cfg.q_dim), dtype),
        "wk": L.normal_init(keys[1], (d, cfg.kv_dim), dtype),
        "wv": L.normal_init(keys[2], (d, cfg.kv_dim), dtype),
        "wo": L.normal_init(keys[3], (cfg.q_dim, d), dtype),
    }
    if cfg.sandwich_norm:
        p["ln1_post"] = L.rmsnorm_init(d, jnp.float32)
        p["ln2_post"] = L.rmsnorm_init(d, jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(cfg.head_dim, jnp.float32)
        p["k_norm"] = L.rmsnorm_init(cfg.head_dim, jnp.float32)
    if cfg.moe:
        p["ffn"] = moe_init(keys[4], cfg, dtype)
    else:
        p["ffn"] = L.swiglu_init(keys[4], d, cfg.d_ff, dtype)
    return p


def init_params(key, cfg):
    dtype = L.dt(cfg.param_dtype)
    k_emb, k_layers, k_head, k_patch = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": L.normal_init(k_emb, (cfg.vocab_size, cfg.d_model), dtype, stddev=0.02),
        "layers": jax.vmap(partial(_layer_init, cfg=cfg, dtype=dtype))(layer_keys),
        "final_norm": L.rmsnorm_init(cfg.d_model, jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.normal_init(k_head, (cfg.vocab_size, cfg.d_model), dtype,
                                       stddev=0.02)
    if cfg.fused_patches:
        params["patch_proj"] = L.normal_init(k_patch, (cfg.patch_dim, cfg.d_model), dtype)
    return params


def layer_windows(cfg):
    """Per-layer sliding window (0 = full/global attention), scanned xs."""
    if cfg.layer_pattern == "local_global":
        # gemma2: even layers local (sliding window), odd layers global
        return jnp.asarray(
            [cfg.sliding_window if i % 2 == 0 else 0 for i in range(cfg.n_layers)],
            jnp.int32)
    return jnp.zeros((cfg.n_layers,), jnp.int32)


# --------------------------------------------------------------------------
# attention sub-block
# --------------------------------------------------------------------------

def _qkv(p, xn, cfg, positions, mi=None):
    cdt = L.dt(cfg.compute_dtype)
    B, S, d = xn.shape
    xc = xn.astype(cdt)
    if mi and mi.active:
        cq = lambda t: mi.constraint(t, P(mi.dp(), None, mi.model_axis))
        wg = lambda w: mi.wgather(w.astype(cdt), 1)
    else:
        cq = lambda t: t
        wg = lambda w: w.astype(cdt)
    q = cq(xc @ wg(p["wq"])).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = cq(xc @ wg(p["wk"])).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = cq(xc @ wg(p["wv"])).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    inv_freq, rot_dim = L.rope_frequencies(cfg.head_dim, cfg.rotary_pct, cfg.rope_theta)
    q = L.apply_rope(q, positions, inv_freq, rot_dim)
    k = L.apply_rope(k, positions, inv_freq, rot_dim)
    return q, k, v


def _cp_attention(q, k, v, window, cfg, mi: MeshInfo):
    """Context-parallel causal attention: q seq-sharded over ``model``,
    KV replicated inside the shard_map region."""
    kwargs = dict(softcap=cfg.attn_softcap,
                  block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv)

    def masked_attn(qc, kc, vc, w, q_off):
        # window w is a traced per-layer scalar; blockwise_attention takes a
        # static window, so fold the traced window into the mask by position
        # arithmetic: attend iff (k<=q) and (w<=0 or q-k<w).
        return _blockwise_traced_window(qc, kc, vc, w, q_off, **kwargs)

    if not mi.active:
        return masked_attn(q, k, v, window, jnp.int32(0))

    dp = mi.dp()
    spec_q = P(dp, mi.model_axis, None, None)
    spec_kv = P(dp, None, None, None)

    def shard_fn(qc, kc, vc, w):
        idx = lax.axis_index(mi.model_axis)
        q_off = idx * qc.shape[1]
        return masked_attn(qc, kc, vc, w, q_off)

    return shard_map(shard_fn, mesh=mi.mesh,
                     in_specs=(spec_q, spec_kv, spec_kv, P()),
                     out_specs=spec_q, check_vma=False)(q, k, v, window)


def _blockwise_traced_window(q, k, v, window, q_offset, *, softcap, block_q, block_kv):
    """blockwise_attention variant whose sliding window is a traced scalar
    (needed because the window is a scanned per-layer value).

    Block loops are PYTHON loops, not lax.scan: XLA cost_analysis counts a
    while-loop body once regardless of trip count, and the roofline needs
    exact per-step FLOP/byte/collective counts (EXPERIMENTS.md §Roofline).
    The only loop left in the whole step is the (optional) layer scan,
    corrected by unroll extrapolation in the dry-run."""
    import math as _m
    B, Sq, H, D = q.shape
    _, Skv0, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / _m.sqrt(D)
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv0)
    # ragged tails: pad q rows (sliced off at the end) and kv columns
    # (masked via k_pos < Skv0)
    Sq_pad = -(-Sq // block_q) * block_q
    Skv = -(-Skv0 // block_kv) * block_kv
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    if Skv != Skv0:
        k = jnp.pad(k, ((0, 0), (0, Skv - Skv0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv - Skv0), (0, 0), (0, 0)))
    nq, nk = Sq_pad // block_q, Skv // block_kv

    outs = []
    for qi in range(nq):
        q_blk = lax.slice_in_dim(q, qi * block_q, (qi + 1) * block_q, axis=1)
        q_blk = q_blk.reshape(B, block_q, KVH, G, D)
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)
        m = jnp.full((B, KVH, G, block_q), L._NEG, jnp.float32)
        l = jnp.zeros((B, KVH, G, block_q), jnp.float32)
        acc = jnp.zeros((B, KVH, G, block_q, D), jnp.float32)
        for kj in range(nk):
            k_blk = lax.slice_in_dim(k, kj * block_kv, (kj + 1) * block_kv,
                                     axis=1)
            v_blk = lax.slice_in_dim(v, kj * block_kv, (kj + 1) * block_kv,
                                     axis=1)
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            k_pos = kj * block_kv + jnp.arange(block_kv)
            ok = k_pos[None, :] <= q_pos[:, None]
            ok &= (window <= 0) | (q_pos[:, None] - k_pos[None, :] < window)
            ok &= (k_pos < Skv0)[None, :]  # ragged kv tail
            okb = ok[None, None, None]
            m_new = jnp.maximum(m, jnp.where(okb, s, L._NEG).max(axis=-1))
            p = jnp.where(okb, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            m = m_new
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(jnp.moveaxis(out, 3, 1).reshape(B, block_q, H, D)
                    .astype(q.dtype))
    return jnp.concatenate(outs, axis=1)[:, :Sq]


# --------------------------------------------------------------------------
# one transformer layer (shared by train / prefill / decode)
# --------------------------------------------------------------------------

def _layer(p, x, window, cfg, mi: MeshInfo, positions, mode,
           kv_cache=None, lengths=None):
    """Returns (x_out, aux_loss, new_kv_cache_slice)."""
    cdt = L.dt(cfg.compute_dtype)
    dp = mi.dp() if mi.active else None
    xn = L.rmsnorm(p["ln1"], x, cfg.norm_eps)

    new_cache = None
    if mode == "decode":
        # x: (B, 1, d); kv_cache: (k, v) each (B, S, KVH, D); lengths: (B,)
        q, k_new, v_new = _qkv(p, xn, cfg, positions, mi)
        k_cache, v_cache = kv_cache
        bidx = jnp.arange(x.shape[0])
        k_cache = k_cache.at[bidx, lengths].set(k_new[:, 0])
        v_cache = v_cache.at[bidx, lengths].set(v_new[:, 0])
        if mi.active:
            k_cache = mi.constraint(k_cache, P(dp, mi.model_axis, None, None))
            v_cache = mi.constraint(v_cache, P(dp, mi.model_axis, None, None))
        new_cache = (k_cache, v_cache)
        attn = L.decode_attention(q[:, 0], k_cache, v_cache, lengths + 1,
                                  window=window, softcap=cfg.attn_softcap)[:, None]
    else:
        q, k, v = _qkv(p, xn, cfg, positions, mi)
        if mode == "prefill":
            new_cache = (k, v)
        attn = _cp_attention(q, k, v, window, cfg, mi)
        if mi.active:  # keep the attention output sequence-sharded into wo
            attn = mi.constraint(attn, P(dp, mi.model_axis, None, None))

    B, S = x.shape[:2]
    attn = attn.reshape(B, S, cfg.q_dim).astype(cdt)
    wo = mi.wgather(p["wo"].astype(cdt), 0) if mi.active \
        else p["wo"].astype(cdt)
    attn_out = (attn @ wo).astype(x.dtype)
    if cfg.sandwich_norm:
        attn_out = L.rmsnorm(p["ln1_post"], attn_out, cfg.norm_eps)
    x = x + attn_out
    if mi.active:
        x = mi.constraint(x, P(dp, None, None))

    xn2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.float32(0)
    ffn_constrain = (lambda t: mi.constraint(t, P(dp, None, mi.model_axis))) \
        if mi.active else None
    ffn_wgather = mi.wgather if mi.active else None
    if cfg.moe:
        ff, aux = moe_ffn(p["ffn"], xn2, cfg, cdt, mi=mi)
    else:
        ff = L.swiglu(p["ffn"], xn2, cdt, constrain=ffn_constrain,
                      wgather=ffn_wgather).astype(x.dtype)
    if cfg.sandwich_norm:
        ff = L.rmsnorm(p["ln2_post"], ff, cfg.norm_eps)
    x = x + ff
    if mi.active:
        x = mi.constraint(x, P(dp, None, None))
    return x, aux, new_cache


# --------------------------------------------------------------------------
# embeddings (with the early-fusion stub) and the three entry points
# --------------------------------------------------------------------------

def embed_inputs(params, tokens, cfg, mi: MeshInfo, patches=None):
    cdt = L.dt(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if cfg.sandwich_norm:  # gemma scales embeddings by sqrt(d)
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    if cfg.fused_patches and patches is not None:
        pe = (patches.astype(cdt) @ params["patch_proj"].astype(cdt))
        x = jnp.concatenate([pe, x[:, cfg.fused_patches:]], axis=1)
    if mi.active:
        x = mi.constraint(x, P(mi.dp(), None, None))
    return x


def _run_layers(params, x, cfg, mi, positions, mode, caches=None, lengths=None):
    windows = layer_windows(cfg)
    remat = cfg.remat and mode == "train"

    def body(x, scanned):
        p, w = scanned[0], scanned[1]
        cache_in = scanned[2] if mode == "decode" else None
        xo, aux, cache_out = _layer(p, x, w, cfg, mi, positions, mode,
                                    kv_cache=cache_in, lengths=lengths)
        return xo, (aux, cache_out)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    if mode == "decode":
        xs = (params["layers"], windows, caches)
    else:
        xs = (params["layers"], windows, None)

    if cfg.scan_layers:
        x, (auxs, new_caches) = lax.scan(body, x, xs)
        return x, auxs.sum(), new_caches

    # python-unrolled layers: exact HLO cost counts (dry-run extrapolation
    # variant; also usable for small models).
    auxs, cache_slices = [], []
    for i in range(cfg.n_layers):
        xs_i = jax.tree.map(lambda a: a[i], xs)
        x, (aux, cache_out) = body(x, xs_i)
        auxs.append(aux)
        cache_slices.append(cache_out)
    new_caches = None
    if mode in ("decode", "prefill"):
        new_caches = jax.tree.map(lambda *cs: jnp.stack(cs), *cache_slices)
    return x, sum(auxs), new_caches


def forward_train(params, batch, cfg, mi: MeshInfo):
    """batch: tokens (B,S) int32, targets (B,S) int32, mask (B,S) f32,
    optional patches (B,P,patch_dim). Returns (loss, metrics)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_inputs(params, tokens, cfg, mi, batch.get("patches"))
    positions = jnp.arange(S)[None, :]
    x, aux, _ = _run_layers(params, x, cfg, mi, positions, "train")
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    # LM head stays vocab-sharded over `model`; the FSDP d-dim is gathered
    # in bf16 (same reasoning as MeshInfo.wgather).
    head = mi.wgather(head.astype(x.dtype), 0) if mi.active else head
    loss_sum, weight = L.chunked_softmax_xent(
        x, head, batch["targets"], batch["mask"].astype(jnp.float32),
        softcap=cfg.final_softcap)
    loss = loss_sum / jnp.maximum(weight, 1.0) + aux
    return loss, {"nll": loss_sum / jnp.maximum(weight, 1.0), "aux": aux,
                  "tokens": weight}


def prefill(params, tokens, cfg, mi: MeshInfo, patches=None, pad_to=None):
    """Run the prompt, build the KV cache. Returns (caches, last_logits).
    caches: (k, v) stacked over layers: (L, B, S_cache, KVH, D)."""
    B, S = tokens.shape
    x = embed_inputs(params, tokens, cfg, mi, patches)
    positions = jnp.arange(S)[None, :]
    x, _, caches = _run_layers(params, x, cfg, mi, positions, "prefill")
    k, v = caches
    if pad_to and pad_to > S:
        pad = [(0, 0), (0, 0), (0, pad_to - S), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    if mi.active:
        spec = P(None, mi.dp(), mi.model_axis, None, None)
        k, v = mi.constraint(k, spec), mi.constraint(v, spec)
    x = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    head = mi.wgather(head.astype(x.dtype), 0) if mi.active else head
    logits = jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return (k, v), logits[:, 0]


def decode_step(params, caches, lengths, last_tokens, cfg, mi: MeshInfo):
    """One serving step: append ``last_tokens`` (B,) at ``lengths`` (B,) and
    predict the next token. Returns (new_caches, logits (B,V))."""
    x = jnp.take(params["embed"], last_tokens[:, None], axis=0)
    cdt = L.dt(cfg.compute_dtype)
    x = x.astype(cdt)
    if cfg.sandwich_norm:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    if mi.active:
        x = mi.constraint(x, P(mi.dp(), None, None))
    positions = lengths[:, None]
    x, _, new_caches = _run_layers(params, x, cfg, mi, positions, "decode",
                                   caches=caches, lengths=lengths)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    head = mi.wgather(head.astype(x.dtype), 0) if mi.active else head
    logits = jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)[:, 0]
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return new_caches, logits
