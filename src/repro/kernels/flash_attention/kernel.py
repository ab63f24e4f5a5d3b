"""Pallas TPU flash attention (fwd): online-softmax over VMEM tiles.

Grid (B*KVH*G, nq, nk): each (head, q-block) revisits its output across
the nk dimension with f32 VMEM scratch accumulators (m, l, acc); the
final kv step normalizes and writes bf16. BlockSpec tiles: q/out
(block_q, D), k/v (block_kv, D) — MXU-aligned for D in {64, 128, 256}.
Supports causal masking, sliding window and logit softcap (gemma2), and
GQA via the flattened (B, KVH, G) head grid.

Validated in interpret mode against ref.py (tests/test_kernels_flash.py);
on TPU the same pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -0.7 * jnp.finfo(jnp.float32).max


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               scale, causal, window, softcap, block_q, block_kv, nk,
               seq_q, seq_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)  # (block_q, D)
    k = k_ref[0].astype(jnp.float32)  # (block_kv, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_kv), 1)
    ok = (q_pos < seq_q) & (k_pos < seq_kv)
    if causal:
        ok &= k_pos <= q_pos
    if window:
        ok &= q_pos - k_pos < window

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(jnp.where(ok, s, NEG), axis=-1))
    p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
    v = v_ref[0].astype(jnp.float32)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] /
                    jnp.maximum(l_sc[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                              "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_kv=128, interpret: bool):
    """q: (B, Sq, H, D); k, v: (B, Skv, KVH, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_kv)
    Sq_pad, Skv_pad = nq * block_q, nk * block_kv
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    if Skv_pad != Skv:
        k = jnp.pad(k, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))

    # flatten heads: q (B*KVH*G, Sq, D); kv (B*KVH, Skv, D)
    qf = jnp.moveaxis(q.reshape(B, Sq_pad, KVH, G, D), 1, 3) \
        .reshape(B * KVH * G, Sq_pad, D)
    kf = jnp.moveaxis(k, 1, 2).reshape(B * KVH, Skv_pad, D)
    vf = jnp.moveaxis(v, 1, 2).reshape(B * KVH, Skv_pad, D)

    grid = (B * KVH * G, nq, nk)
    kern = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, nk=nk,
        seq_q=Sq, seq_kv=Skv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda h, qi, ki, G=G: (h // G, ki, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda h, qi, ki, G=G: (h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KVH * G, Sq_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
            pltpu.VMEM((block_q, D), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, KVH, G, Sq_pad, D)
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq_pad, H, D)[:, :Sq]
