"""Jit'd public wrapper for the harmonize kernel (and its oracle)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.harmonize.kernel import LANES, harmonize_pallas
from repro.kernels.harmonize.ref import harmonize_ref


@functools.partial(jax.jit, static_argnames=("tick_s", "n_ticks",
                                             "use_pallas"))
def harmonize(values, timestamps, valid, window_start, *, tick_s: float,
              n_ticks: int, use_pallas: bool = True):
    """Batched entry: (E, S, M) raw samples -> (E, S, T) tick means.

    window_start: (E,). Returns (values (E,S,T), observed (E,S,T)).
    """
    E, S, M = values.shape
    R = E * S
    t0 = jnp.broadcast_to(window_start[:, None], (E, S)).reshape(R, 1)
    if not use_pallas:
        out, obs = harmonize_ref(
            values.reshape(R, M).astype(jnp.float32),
            timestamps.reshape(R, M).astype(jnp.float32),
            valid.reshape(R, M).astype(jnp.float32) > 0, t0[:, 0], tick_s,
            n_ticks)
        return out.reshape(E, S, n_ticks), obs.reshape(E, S, n_ticks)
    pad = (-R) % LANES

    def lanes(x, n):   # (R, n) -> (n, R + pad): rows onto lanes
        x = x.reshape(R, n).astype(jnp.float32).T
        return jnp.pad(x, ((0, 0), (0, pad))) if pad else x

    out, obs = harmonize_pallas(lanes(values, M), lanes(timestamps, M),
                                lanes(valid, M), lanes(t0, 1),
                                tick_s=tick_s, n_ticks=n_ticks)
    unlanes = lambda x: x[:, :R].T.reshape(E, S, n_ticks)
    return unlanes(out), unlanes(obs)
