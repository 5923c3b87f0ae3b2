"""Window aggregation + cross-stream relationships (the Manager's logic).

"It can prioritize the most recent entries, but it can also apply
aggregation logic, such as calculating sums, averages ... the Manager
analyzes the data to identify meaningful relationships within it. For
instance, it may combine temperature readings from sensors of various
brands within the same area to compute a weighted average."

``combine`` implements exactly that: a (features x streams) weight matrix
mapping harmonized per-tick streams to derived features — weighted averages
across same-area sensors, sums across feeders, etc.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import f32

AGGS = ("last", "mean", "sum", "min", "max", "std", "count")

# repro.kernels.window_agg stats-column layout:
# [mean, var, min, max, last, count, sum, n_spikes]
_KERNEL_COLS = {"mean": 0, "min": 2, "max": 3, "last": 4, "count": 5,
                "sum": 6}


def window_agg(values, mask, agg: str, *, use_pallas: bool = False):
    """Aggregate the tick dim away. values/mask: (E, S, T) -> (E, S).

    ``use_pallas=True`` computes every aggregate from one pass of the fused
    ``repro.kernels.window_agg`` kernel (all eight window stats in a single
    VMEM tile walk; interpret mode off-TPU) instead of a per-agg XLA
    reduction; empty windows are fixed up to this module's conventions
    (min/max saturate, the rest are 0).
    """
    w = mask.astype(jnp.float32)
    n = w.sum(-1)
    big = jnp.float32(3.4e38)
    if use_pallas and (agg == "std" or agg in _KERNEL_COLS):
        from repro.kernels.window_agg.ops import window_agg as agg_kernel
        E, S = values.shape[:2]
        zeros = jnp.zeros((E, S), jnp.float32)
        stats, _ = agg_kernel(values, mask, zeros, zeros + 1.0,
                              use_pallas=True)
        if agg == "std":
            return jnp.sqrt(stats[..., 1])
        out = stats[..., _KERNEL_COLS[agg]]
        # the kernel zeroes empty-window min/max; this module saturates
        if agg == "min":
            return jnp.where(n > 0, out, big)
        if agg == "max":
            return jnp.where(n > 0, out, -big)
        return out
    if agg == "last":
        idx = jnp.where(mask, jnp.arange(values.shape[-1]), -1).max(-1)
        take = jnp.take_along_axis(values, jnp.maximum(idx, 0)[..., None], -1)[..., 0]
        return jnp.where(idx >= 0, take, 0.0)
    if agg == "mean":
        return f32.einsum("est,est->es", values, w) / jnp.maximum(n, 1)
    if agg == "sum":
        return f32.einsum("est,est->es", values, w)
    if agg == "min":
        return jnp.min(jnp.where(mask, values, big), -1)
    if agg == "max":
        return jnp.max(jnp.where(mask, values, -big), -1)
    if agg == "std":
        m = f32.einsum("est,est->es", values, w) / jnp.maximum(n, 1)
        v = f32.einsum("est,est->es", jnp.square(values - m[..., None]), w)
        return jnp.sqrt(v / jnp.maximum(n, 1))
    if agg == "count":
        return n
    raise ValueError(agg)


def combine(values, weights):
    """Cross-stream relationships. values (E,S,T) x weights (F,S) -> (E,F,T).

    Rows of ``weights`` are derived features: a row with 1/k over k
    temperature streams is the paper's weighted-average example; a row of
    ones over feeder streams is a total-consumption sum.
    """
    return f32.einsum("est,fs->eft", values, weights)


def feature_vector(values, mask, weights, *, per_tick: bool = False,
                   feature_agg: str = "last", use_pallas: bool = False):
    """Full Manager output: derived features flattened for the Encoder.

    values/mask (E,S,T), weights (F,S) ->
      per_tick=False: (E, F) per-window features — the value at the final
        tick position when ``feature_agg="last"`` (the original shape of
        the pipeline output), else each stream's window aggregate
        (:func:`window_agg`, e.g. "mean"/"sum") combined through
        ``weights``; ``use_pallas`` routes that aggregate through the
        fused kernel
      per_tick=True : (E, F*T) the whole harmonized window
    """
    if per_tick:
        feats = combine(values, weights)                 # (E, F, T)
        E = feats.shape[0]
        return feats.reshape(E, -1)
    if feature_agg != "last":
        per_stream = window_agg(values, mask, feature_agg,
                                use_pallas=use_pallas)   # (E, S)
        return f32.einsum("es,fs->ef", per_stream, weights)
    return combine(values, weights)[..., -1]
