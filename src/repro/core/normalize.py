"""Streaming normalization so "data can be effectively used by models".

Running per-(env, stream) statistics with Welford-style merging of each
window's batch statistics; z-score or min-max normalization; exact
denormalization for decoding model outputs back to engineering units.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import f32


class NormState(NamedTuple):
    count: jax.Array  # (E, S)
    mean: jax.Array
    m2: jax.Array     # sum of squared deviations
    min: jax.Array
    max: jax.Array


def init_state(E, S) -> NormState:
    z = jnp.zeros((E, S), jnp.float32)
    return NormState(z, z, z, jnp.full((E, S), jnp.inf, jnp.float32),
                     jnp.full((E, S), -jnp.inf, jnp.float32))


def update(state: NormState, values, observed) -> NormState:
    """Chan/Welford parallel merge of this window's stats into the running
    stats — one vectorized step per window, no per-sample loop."""
    w = observed.astype(jnp.float32)
    nb = w.sum(-1)
    mb = f32.einsum("est,est->es", values, w) / jnp.maximum(nb, 1)
    m2b = f32.einsum("est,est->es", jnp.square(values - mb[..., None]), w)
    na = state.count
    n = na + nb
    delta = mb - state.mean
    mean = jnp.where(n > 0, state.mean + delta * nb / jnp.maximum(n, 1), state.mean)
    m2 = state.m2 + m2b + jnp.square(delta) * na * nb / jnp.maximum(n, 1)
    big = jnp.float32(3.4e38)
    vmin = jnp.minimum(state.min, jnp.min(jnp.where(observed, values, big), -1))
    vmax = jnp.maximum(state.max, jnp.max(jnp.where(observed, values, -big), -1))
    has = nb > 0
    return NormState(
        count=n,
        mean=mean,
        m2=jnp.where(has, m2, state.m2),
        min=jnp.where(has, vmin, state.min),
        max=jnp.where(has, vmax, state.max),
    )


def sigma(state: NormState):
    return jnp.sqrt(jnp.maximum(state.m2 / jnp.maximum(state.count - 1, 1), 1e-12))


def znorm(state: NormState, values):
    """values (E, S, ...) -> z-scores using running stats."""
    ex = (...,) + (None,) * (values.ndim - 2)
    return (values - state.mean[ex]) / jnp.maximum(sigma(state)[ex], 1e-6)


def denorm_z(state: NormState, z):
    ex = (...,) + (None,) * (z.ndim - 2)
    return z * jnp.maximum(sigma(state)[ex], 1e-6) + state.mean[ex]


def minmax(state: NormState, values):
    ex = (...,) + (None,) * (values.ndim - 2)
    span = jnp.maximum(state.max[ex] - state.min[ex], 1e-6)
    return jnp.clip((values - state.min[ex]) / span, 0.0, 1.0)


def denorm_minmax(state: NormState, u):
    ex = (...,) + (None,) * (u.ndim - 2)
    span = jnp.maximum(state.max[ex] - state.min[ex], 1e-6)
    return u * span + state.min[ex]
