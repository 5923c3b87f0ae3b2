"""Pallas TPU kernel: fused window statistics + anomaly mask.

This is the Manager's hot loop at fleet scale — thousands of environments x
streams per tick. One VMEM pass over each (row, tick) tile produces all
eight statistics AND the spike mask, instead of the eight separate
reductions (8x HBM reads) the unfused pipeline issues.

Layout: rows = E*S flattened, ticks padded to the 128-lane boundary. Blocks
are (ROWS_BLK, T_pad) in VMEM; the stats output is (ROWS_BLK, 128) with the
first N_STATS lanes used (TPU stores need full lanes — documented waste).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call
from repro.kernels.window_agg.ref import N_STATS

ROWS_BLK = 8
LANES = 128


def _kernel(values_ref, mask_ref, mean_ref, var_ref, stats_ref, spikes_ref,
            *, k_sigma: float):
    v = values_ref[...].astype(jnp.float32)          # (R, T)
    m = mask_ref[...] > 0
    w = m.astype(jnp.float32)
    n = w.sum(-1)
    s = (v * w).sum(-1)
    mean = s / jnp.maximum(n, 1.0)
    var = (jnp.square(v - mean[:, None]) * w).sum(-1) / jnp.maximum(n, 1.0)
    big = jnp.float32(3.4e38)
    vmin = jnp.where(n > 0, jnp.min(jnp.where(m, v, big), -1), 0.0)
    vmax = jnp.where(n > 0, jnp.max(jnp.where(m, v, -big), -1), 0.0)
    T = v.shape[-1]
    tick_idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    idx = jnp.max(jnp.where(m, tick_idx, -1), -1)
    onehot = (tick_idx == idx[:, None]) & m
    last = (v * onehot.astype(jnp.float32)).sum(-1)

    # state refs are (R, 1) blocks — broadcast directly against (R, T)
    sigma = jnp.sqrt(jnp.maximum(var_ref[...].astype(jnp.float32), 1e-12))
    z = jnp.abs(v - mean_ref[...].astype(jnp.float32)) / sigma
    spikes = m & (z > k_sigma)
    spikes_ref[...] = spikes.astype(jnp.float32)

    cols = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], LANES), 1)
    stat_rows = [mean, var, vmin, vmax, last, n, s,
                 spikes.sum(-1).astype(jnp.float32)]
    out = jnp.zeros((v.shape[0], LANES), jnp.float32)
    for i, sr in enumerate(stat_rows):
        out = jnp.where(cols == i, sr[:, None], out)
    stats_ref[...] = out


def window_agg_pallas(values, mask, state_mean, state_var, *,
                      k_sigma: float = 6.0):
    """values/mask: (R, T); state_mean/var: (R, 1) f32 (lane-padded)."""
    R, T = values.shape
    assert R % ROWS_BLK == 0, R
    grid = (R // ROWS_BLK,)
    kern = functools.partial(_kernel, k_sigma=k_sigma)
    stats, spikes = pallas_call(
        kern,
        name="window_agg",
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_BLK, T), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_BLK, T), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_BLK, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_BLK, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS_BLK, LANES), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_BLK, T), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, LANES), jnp.float32),
            jax.ShapeDtypeStruct((R, T), jnp.float32),
        ],
    )(values, mask, state_mean, state_var)
    return stats[:, :N_STATS], spikes > 0
