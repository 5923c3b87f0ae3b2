"""Jit'd public wrapper for the RG-LRU scan kernel.

When reached from the decision path (the registry's ``policy="rglru"``
with ``use_pallas=True``, B = n_envs, T = 1), the ``pallas_call`` here is
statically certifiable: ``analysis/jaxpr_check`` evaluates the BlockSpec
index maps over the grid and checks the env-tagged batch axis is tiled
in size-1 blocks routed identically across inputs and outputs
(``pallas-env-block``), then walks the kernel body itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rglru_scan.kernel import LANES, rglru_scan_pallas
from repro.kernels.rglru_scan.ref import rglru_scan_ref


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def rglru_scan(a, b, h0, *, use_pallas: bool = True):
    """h_t = a_t h_{t-1} + b_t over axis 1. a, b: (B, T, W); h0: (B, W)."""
    if not use_pallas:
        return rglru_scan_ref(a, b, h0)
    B, T, W = a.shape
    pad = (-W) % LANES
    if pad:
        zp3 = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
        a, b = zp3(a), zp3(b)
        h0 = jnp.pad(h0, ((0, 0), (0, pad)))
    out, hlast = rglru_scan_pallas(a.astype(jnp.float32),
                                   b.astype(jnp.float32),
                                   h0.astype(jnp.float32).reshape(B, 1, -1))
    hlast = hlast.reshape(B, -1)
    if pad:
        out, hlast = out[..., :W], hlast[..., :W]
    return out, hlast
