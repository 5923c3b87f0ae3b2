"""Data-rate harmonization — Percepta's core stream transformation.

Sources report at wildly different rates ("one device may send data every 5
minutes while another sends it once per hour") with arbitrary jitter.
``harmonize`` aligns every stream onto the model's tick grid:

  * tick t collects samples with timestamp in (tick_ts[t] - tick, tick_ts[t]]
  * multiple samples per tick are aggregated (mean/last/sum/min/max)
  * ticks with no sample are marked unobserved (gap-filling handles them)
  * alternatively ``mode='interp'`` linearly interpolates between the two
    samples bracketing the tick (for slow, smooth quantities)

Everything is vectorized over (E, S, M) x (T,): the bucket assignment is a
searchsorted-free one-hot contraction, which is what the Pallas
``kernels/harmonize`` kernel tiles through VMEM on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import f32
from repro.core.frame import RawWindow

AGGS = ("mean", "last", "sum", "min", "max")


def tick_grid(window_start, tick_s: float, n_ticks: int):
    """Tick timestamps (end-of-bucket convention). window_start: (E,)."""
    return window_start[:, None] + tick_s * (1.0 + jnp.arange(n_ticks))


def bucketize(raw: RawWindow, tick_ts, tick_s: float):
    """Bucket index per raw sample. Returns (idx (E,S,M), in_range (E,S,M))."""
    t0 = tick_ts[:, 0] - tick_s  # window start
    rel = raw.timestamps - t0[:, None, None]
    idx = jnp.ceil(rel / tick_s).astype(jnp.int32) - 1
    T = tick_ts.shape[1]
    ok = raw.valid & (idx >= 0) & (idx < T)
    return jnp.clip(idx, 0, T - 1), ok


# Below this many one-hot elements per (E,S) row, the dense contraction in
# ``_harmonize_dense`` beats segment scatter. XLA:CPU lowers segment_sum to
# a serial per-element scatter loop (~350us for 4k updates — measured inside
# the scan engine); the dense mask ops vectorize and fuse. Edge windows
# (M<=64, T<=16) always take the dense path; the scatter path remains for
# large M*T where one-hot memory would dominate.
_DENSE_MT_MAX = 8192


def _harmonize_dense(values, timestamps, idx, ok, T: int, agg: str):
    """One-hot-mask aggregation for one requested ``agg`` (small M*T).

    Layout matters on XLA:CPU: reducing the (E,S,M,T) one-hot over its
    strided M axis is ~6x slower than phrasing the same sum as a dot or
    reducing a contiguous trailing axis (measured inside the scan engine).
    Sums therefore go through einsum; min/max/last build the mask directly
    as (E,S,T,M) so the reduce runs over the innermost axis.
    """
    big = jnp.float32(3.4e38)
    if agg in ("mean", "sum"):
        w = ((idx[..., None] == jnp.arange(T))
             & ok[..., None]).astype(jnp.float32)               # (E,S,M,T)
        # 0/1 operands are exact in bfloat16: default precision suffices
        count = jnp.einsum("esm,esmt->est", jnp.ones_like(values), w)
        observed = count > 0
        total = f32.einsum("esm,esmt->est", values, w)
        out = total if agg == "sum" else total / jnp.maximum(count, 1.0)
        return jnp.where(observed, out, 0.0), observed

    onehot = (idx[:, :, None, :] == jnp.arange(T)[:, None]) \
        & ok[:, :, None, :]                                     # (E,S,T,M)
    count = onehot.astype(jnp.float32).sum(-1)                  # (E,S,T)
    observed = count > 0
    v_tm = values[:, :, None, :]
    if agg == "min":
        out = jnp.min(jnp.where(onehot, v_tm, big), axis=-1)
    elif agg == "max":
        out = jnp.max(jnp.where(onehot, v_tm, -big), axis=-1)
    elif agg == "last":
        ts_key = jnp.where(onehot, timestamps[:, :, None, :], -big)
        last_sel = (ts_key == ts_key.max(axis=-1, keepdims=True)) & onehot
        sel = last_sel.astype(jnp.float32)
        out = (v_tm * sel).sum(-1) / jnp.maximum(sel.sum(-1), 1.0)
    else:
        raise ValueError(agg)
    return jnp.where(observed, out, 0.0), observed


def harmonize_segment(raw: RawWindow, tick_ts, tick_s: float,
                      agg: str = "mean"):
    """Segment-reduction harmonization: O(M) per sample instead of the
    O(M*T) one-hot contraction (the §Perf pipeline optimization; same
    results as ``harmonize`` — property-tested).

    Buckets become segment ids (row-major over E*S rows x T ticks; invalid
    samples map to a trash segment) and jax.ops.segment_* does the rest.
    Small windows (M*T <= ``_DENSE_MT_MAX``) instead use a dense mask
    contraction — same bucket sums, vectorized instead of scattered.
    """
    E, S, M = raw.values.shape
    T = tick_ts.shape[1]
    idx, ok = bucketize(raw, tick_ts, tick_s)
    if M * T <= _DENSE_MT_MAX:
        return _harmonize_dense(raw.values, raw.timestamps, idx, ok, T, agg)
    rows = jnp.arange(E * S).reshape(E, S, 1)
    seg = jnp.where(ok, rows * T + idx, E * S * T).reshape(-1)
    n_seg = E * S * T + 1
    v = jnp.where(ok, raw.values, 0.0).reshape(-1)
    okf = ok.astype(jnp.float32).reshape(-1)

    count = jax.ops.segment_sum(okf, seg, num_segments=n_seg)[:-1]
    observed = (count > 0).reshape(E, S, T)
    if agg in ("mean", "sum"):
        total = jax.ops.segment_sum(v, seg, num_segments=n_seg)[:-1]
        out = total if agg == "sum" else total / jnp.maximum(count, 1.0)
    elif agg == "min":
        out = jax.ops.segment_min(
            jnp.where(ok, raw.values, 3.4e38).reshape(-1), seg,
            num_segments=n_seg)[:-1]
    elif agg == "max":
        out = jax.ops.segment_max(
            jnp.where(ok, raw.values, -3.4e38).reshape(-1), seg,
            num_segments=n_seg)[:-1]
    elif agg == "last":
        ts = jnp.where(ok, raw.timestamps, -3.4e38).reshape(-1)
        bucket_last = jax.ops.segment_max(ts, seg, num_segments=n_seg)
        is_last = (ts == bucket_last[seg]) & (okf > 0)
        den = jax.ops.segment_sum(is_last.astype(jnp.float32), seg,
                                  num_segments=n_seg)[:-1]
        num = jax.ops.segment_sum(v * is_last, seg, num_segments=n_seg)[:-1]
        out = num / jnp.maximum(den, 1.0)
    else:
        raise ValueError(agg)
    out = out.reshape(E, S, T)
    return jnp.where(observed, out, 0.0), observed


def harmonize(raw: RawWindow, tick_ts, tick_s: float, agg: str = "mean",
              stream_agg=None):
    """Align raw samples to the tick grid (one-hot contraction form).

    raw: (E, S, M); tick_ts: (E, T). agg: default aggregation; stream_agg:
    optional (S,) int32 selecting AGGS per stream (heterogeneous sources).
    Returns (values (E,S,T), observed (E,S,T)).
    """
    E, S, M = raw.values.shape
    T = tick_ts.shape[1]
    idx, ok = bucketize(raw, tick_ts, tick_s)
    onehot = (idx[..., None] == jnp.arange(T)) & ok[..., None]  # (E,S,M,T)
    w = onehot.astype(jnp.float32)
    count = w.sum(axis=2)                                       # (E,S,T)
    observed = count > 0

    v = raw.values
    sum_v = f32.einsum("esm,esmt->est", v, w)
    mean_v = sum_v / jnp.maximum(count, 1.0)
    big = jnp.float32(3.4e38)
    min_v = jnp.min(jnp.where(onehot, v[..., None], big), axis=2)
    max_v = jnp.max(jnp.where(onehot, v[..., None], -big), axis=2)
    # last = sample with max timestamp within the bucket
    ts_key = jnp.where(onehot, raw.timestamps[..., None], -big)
    last_sel = ts_key == ts_key.max(axis=2, keepdims=True)
    last_v = f32.einsum("esm,esmt->est", v,
                        (last_sel & onehot).astype(jnp.float32)) / \
        jnp.maximum((last_sel & onehot).sum(axis=2), 1)

    stack = jnp.stack([mean_v, last_v, sum_v, min_v, max_v])    # (5,E,S,T)
    if stream_agg is None:
        out = stack[AGGS.index(agg)]
    else:
        out = jnp.take_along_axis(
            stack, stream_agg[None, None, :, None], axis=0)[0]
    out = jnp.where(observed, out, 0.0)
    return out, observed


def harmonize_interp(raw: RawWindow, tick_ts, *, max_gap_s: float = 0.0,
                     prev_value=None, prev_ts=None):
    """Linear interpolation of each tick between bracketing samples.

    For slow-reporting sources (the paper's once-per-hour devices) bucketing
    leaves most ticks empty; interpolation reconstructs the intermediate
    resolution instead. O(M*T) masked min/max — no sort, batch-friendly.
    prev_value/prev_ts: (E, S) carry-in from the previous window so the first
    ticks can bridge across the window boundary.
    """
    E, S, M = raw.values.shape
    T = tick_ts.shape[1]
    ts = jnp.where(raw.valid, raw.timestamps, jnp.inf)          # (E,S,M)
    tsn = jnp.where(raw.valid, raw.timestamps, -jnp.inf)
    tick = tick_ts[:, None, :, None]                            # (E,1,T,1)
    before = tsn[:, :, None, :] <= tick[..., 0][..., None]      # (E,S,T,M)
    after = ts[:, :, None, :] > tick[..., 0][..., None]

    big = jnp.float32(3.4e38)
    t_lo = jnp.max(jnp.where(before, tsn[:, :, None, :], -big), axis=-1)
    t_hi = jnp.min(jnp.where(after, ts[:, :, None, :], big), axis=-1)
    sel_lo = before & (tsn[:, :, None, :] == t_lo[..., None])
    sel_hi = after & (ts[:, :, None, :] == t_hi[..., None])
    den_lo = jnp.maximum(sel_lo.sum(-1), 1)
    den_hi = jnp.maximum(sel_hi.sum(-1), 1)
    v_lo = f32.einsum("estm,esm->est", sel_lo.astype(jnp.float32), raw.values) / den_lo
    v_hi = f32.einsum("estm,esm->est", sel_hi.astype(jnp.float32), raw.values) / den_hi
    has_lo = t_lo > -big
    has_hi = t_hi < big

    if prev_value is not None and prev_ts is not None:
        bridge = (~has_lo) & (prev_ts[:, :, None] <= tick_ts[:, None, :])
        t_lo = jnp.where(bridge, prev_ts[:, :, None], t_lo)
        v_lo = jnp.where(bridge, prev_value[:, :, None], v_lo)
        has_lo = has_lo | bridge

    span = jnp.maximum(t_hi - t_lo, 1e-6)
    frac = jnp.clip((tick_ts[:, None, :] - t_lo) / span, 0.0, 1.0)
    both = has_lo & has_hi
    if max_gap_s > 0:
        both = both & ((t_hi - t_lo) <= max_gap_s)
    interp = v_lo + frac * (v_hi - v_lo)
    out = jnp.where(both, interp, jnp.where(has_lo, v_lo, 0.0))
    observed = both | has_lo
    return out, observed
