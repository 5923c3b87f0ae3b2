"""Pallas TPU kernel: causal GQA flash attention (+ sliding window, softcap).

VMEM-tiled online-softmax: grid (batch, q_head, q_block, kv_block) with the
(acc, m, l) accumulators in VMEM scratch carried across the kv_block grid
dim (the innermost, 'arbitrary'-order dim on TPU). KV blocks entirely in the
causal future of a Q block are masked (their contribution is exactly zero —
XLA's TPU scheduler skips revisiting them via the index map when
block_causal pruning applies; interpret mode just computes zeros).

GQA is native: the kv index map folds q_head -> q_head // group so KV tiles
are fetched once per kv head group, never materialized repeated. The gemma2
variants are the same kernel with softcap/window static parameters — the
tanh softcap applies pre-masking exactly as in the reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

DEFAULT_QBLK = 128
DEFAULT_KBLK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, window: int, softcap: float, kv_blocks: int,
            q_blk: int, kv_blk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale         # (Qb, D)
    k = k_ref[0, 0].astype(jnp.float32)                 # (Kb, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = q @ k.T                                          # (Qb, Kb)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * kv_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_new = jnp.maximum(m_new, -1e29)  # fully-masked rows stay finite
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, window: int = 0, softcap: float = 0.0,
                           q_blk: int = DEFAULT_QBLK,
                           kv_blk: int = DEFAULT_KBLK):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D). Returns (B, H, S, D)."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    q_blk = min(q_blk, S)
    kv_blk = min(kv_blk, S)
    assert S % q_blk == 0 and S % kv_blk == 0, (S, q_blk, kv_blk)
    nq, nk = S // q_blk, S // kv_blk
    scale = 1.0 / math.sqrt(D)
    kern = functools.partial(_kernel, scale=scale, window=window,
                             softcap=softcap, kv_blocks=nk, q_blk=q_blk,
                             kv_blk=kv_blk)
    grid = (B, H, nq, nk)
    return pallas_call(
        kern,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kv_blk, D),
                         lambda b, h, qi, ki, _G=G: (b, h // _G, ki, 0)),
            pl.BlockSpec((1, 1, kv_blk, D),
                         lambda b, h, qi, ki, _G=G: (b, h // _G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            # VMEM accumulators carried across the kv grid dim
            pltpu.VMEM((q_blk, D), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
        ],
    )(q, k, v)
