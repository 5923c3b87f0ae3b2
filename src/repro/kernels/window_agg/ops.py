"""Jit'd public wrapper for the window_agg kernel (and its oracle)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.window_agg.kernel import ROWS_BLK, window_agg_pallas
from repro.kernels.window_agg.ref import window_agg_ref


def _pad_rows(x, mult):
    r = x.shape[0]
    pad = (-r) % mult
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, pad


@functools.partial(jax.jit, static_argnames=("k_sigma", "use_pallas"))
def window_agg(values, mask, state_mean, state_var, *, k_sigma: float = 6.0,
               use_pallas: bool = True):
    """Batched entry: values/mask (E, S, T); state (E, S).

    Returns (stats (E, S, N_STATS), spikes (E, S, T)).
    """
    E, S, T = values.shape
    v = values.reshape(E * S, T).astype(jnp.float32)
    m = mask.reshape(E * S, T).astype(jnp.float32)
    mu = state_mean.reshape(E * S, 1).astype(jnp.float32)
    var = state_var.reshape(E * S, 1).astype(jnp.float32)
    if not use_pallas:
        stats, spikes = window_agg_ref(v, m > 0, mu[:, 0], var[:, 0], k_sigma)
    else:
        v, pad = _pad_rows(v, ROWS_BLK)
        m, _ = _pad_rows(m, ROWS_BLK)
        mu, _ = _pad_rows(mu, ROWS_BLK)
        var2, _ = _pad_rows(var, ROWS_BLK)
        stats, spikes = window_agg_pallas(v, m, mu, var2, k_sigma=k_sigma)
        if pad:
            stats, spikes = stats[:E * S], spikes[:E * S]
    return stats.reshape(E, S, -1), spikes.reshape(E, S, T)
