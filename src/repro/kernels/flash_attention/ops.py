"""Jit'd public wrapper for flash attention (model-layout adapter)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("window", "softcap",
                                             "use_pallas",
                                             "q_blk", "kv_blk"))
def flash_attention(q, k, v, *, window: int = 0, softcap: float = 0.0,
                    use_pallas: bool = True,
                    q_blk: int = 128, kv_blk: int = 128):
    """Model layout in/out: q (B, S, H, D); k, v (B, S, Hkv, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_pallas:
        out = flash_attention_pallas(qt, kt, vt, window=window,
                                     softcap=softcap,
                                     q_blk=q_blk, kv_blk=kv_blk)
    else:
        out = attention_ref(qt, kt, vt, window=window, softcap=softcap)
    return out.transpose(0, 2, 1, 3)
