"""Anomaly handling — spike detection + replacement before the model.

"...detecting anomalies such as data spikes, and replacing missing values
based on historical patterns or recent observations."

Detection: robust z-score against carried running statistics (mean/var via a
numerically-stable exponential Welford) or median-absolute-deviation within
the window. Replacement: clip to the k-sigma envelope, substitute the
running mean, or mark-as-missing so gap-filling handles it.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import f32

POLICIES = ("clip", "mean", "missing")


class AnomalyState(NamedTuple):
    mean: jax.Array    # (E, S) running mean
    var: jax.Array     # (E, S) running variance
    count: jax.Array   # (E, S)


def init_state(E, S) -> AnomalyState:
    z = jnp.zeros((E, S), jnp.float32)
    return AnomalyState(z, jnp.ones((E, S), jnp.float32), z)


def detect_zscore(values, observed, state: AnomalyState, k_sigma: float = 6.0):
    """Spike where |x - mean| > k * sigma (only once stats have warmed up)."""
    sigma = jnp.sqrt(jnp.maximum(state.var, 1e-12))
    z = jnp.abs(values - state.mean[..., None]) / sigma[..., None]
    warm = (state.count > 8.0)[..., None]
    return observed & warm & (z > k_sigma)


def detect_mad(values, observed, k: float = 8.0):
    """Window-local median-absolute-deviation detector (no state needed)."""
    big = jnp.float32(3.4e38)
    masked = jnp.where(observed, values, jnp.nan)
    med = jnp.nanmedian(masked, axis=-1, keepdims=True)
    mad = jnp.nanmedian(jnp.abs(masked - med), axis=-1, keepdims=True)
    mad = jnp.where(jnp.isnan(mad) | (mad < 1e-9), big, mad)
    dev = jnp.abs(values - jnp.where(jnp.isnan(med), 0.0, med))
    return observed & (dev > k * 1.4826 * mad)


def replace(values, observed, spikes, state: AnomalyState,
            policy: str = "clip", k_sigma: float = 6.0):
    """Returns (values', observed', replaced_mask)."""
    sigma = jnp.sqrt(jnp.maximum(state.var, 1e-12))[..., None]
    mean = state.mean[..., None]
    if policy == "clip":
        clipped = jnp.clip(values, mean - k_sigma * sigma, mean + k_sigma * sigma)
        out = jnp.where(spikes, clipped, values)
        return out, observed, spikes
    if policy == "mean":
        out = jnp.where(spikes, jnp.broadcast_to(mean, values.shape), values)
        return out, observed, spikes
    if policy == "missing":
        return jnp.where(spikes, 0.0, values), observed & ~spikes, spikes
    raise ValueError(policy)


def update_state(state: AnomalyState, values, observed,
                 alpha: float = 0.05) -> AnomalyState:
    """Exponential Welford over clean observed ticks (batched over E, S)."""
    n = observed.sum(-1)
    mean_w = f32.einsum("est,est->es", values, observed.astype(jnp.float32)) \
        / jnp.maximum(n, 1)
    var_w = f32.einsum("est,est->es", jnp.square(values - mean_w[..., None]),
                       observed.astype(jnp.float32)) / jnp.maximum(n, 1)
    has = n > 0
    boot = state.count < 1
    new_mean = jnp.where(boot, mean_w,
                         (1 - alpha) * state.mean + alpha * mean_w)
    new_var = jnp.where(boot, jnp.maximum(var_w, 1e-6),
                        (1 - alpha) * state.var
                        + alpha * (var_w + jnp.square(mean_w - state.mean)))
    return AnomalyState(
        mean=jnp.where(has, new_mean, state.mean),
        var=jnp.where(has, new_var, state.var),
        count=state.count + n,
    )
