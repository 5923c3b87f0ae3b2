"""Gap filling — detect missing ticks and impute them.

"Percepta is capable of detecting missing data and, when necessary, filling
in the gaps to maintain the continuity and reliability of the input data."

Strategies (selectable per stream):
  locf      last observation carried forward (across window boundaries via
            the carried ``last_value`` state)
  linear    bridge interior gaps linearly between observations (falls back
            to locf at the trailing edge)
  ewma      exponentially-weighted mean of past observations (state-carried)
  seasonal  mean of the same tick-of-day from history (state-carried slots)

The LOCF scan is a prefix "latest-observation" propagation — associative, so
it runs as ``jax.lax.associative_scan`` over the tick dim (O(log T) depth).

``use_pallas=True`` routes the ``locf`` strategy through the Pallas kernel
in ``repro.kernels.locf`` (one VMEM pass with the carry in VREGs on TPU;
interpret mode elsewhere). The kernel is pure selection — no arithmetic —
so its fill values are bit-identical to the XLA paths wherever the ``has``
mask is True, which is the only place ``gap_fill`` consumes them.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import f32

STRATEGIES = ("locf", "linear", "ewma", "seasonal")


class GapFillState(NamedTuple):
    last_value: jax.Array   # (E, S) last observed value ever
    last_ts: jax.Array      # (E, S)
    ewma: jax.Array         # (E, S)
    seasonal: jax.Array     # (E, S, K) per time-of-day slot running mean
    seasonal_n: jax.Array   # (E, S, K)


def init_state(E, S, K=24) -> GapFillState:
    z = jnp.zeros((E, S), jnp.float32)
    return GapFillState(z, z - 1e30, z, jnp.zeros((E, S, K), jnp.float32),
                        jnp.zeros((E, S, K), jnp.float32))


# Below this tick count the O(T^2) masked-argmax propagation replaces the
# associative scan: XLA:CPU lowers associative_scan to log2(T) rounds of
# small strided slice/concat ops whose per-op overhead dominates at edge
# window sizes, while the dense form is two vectorized ops and a dot.
_DENSE_T_MAX = 64


def _locf_scan(values, observed, init_value, init_has):
    """Carry (value, has) of the latest observation along the tick axis.

    Positions with no observation at or before them return (0, False) on
    the dense path and (init_value, False) on the scan path — callers mask
    by the ``has`` flag, so the carried value is only meaningful when True.
    """
    v = jnp.concatenate([init_value[..., None].astype(jnp.float32), values],
                        axis=-1)
    o = jnp.concatenate([init_has[..., None], observed], axis=-1)
    T1 = v.shape[-1]
    if T1 <= _DENSE_T_MAX:
        j = jnp.arange(T1)
        tril = j[:, None] >= j[None, :]                      # (T1, T1)
        key = jnp.where(o[..., None, :] & tril, j, -1)       # (..., T1, T1)
        li = key.max(-1)                                     # latest obs <= t
        oh = (li[..., None] == j).astype(jnp.float32)
        cv = f32.einsum("...j,...tj->...t", v, oh)
        return cv[..., 1:], (li >= 0)[..., 1:]

    def combine(a, b):
        av, ao = a
        bv, bo = b
        return jnp.where(bo, bv, av), ao | bo

    cv, co = jax.lax.associative_scan(combine, (v, o), axis=-1)
    return cv[..., 1:], co[..., 1:]


def locf(values, observed, state: GapFillState):
    has_prev = state.last_ts > -1e29
    return _locf_scan(values, observed, state.last_value, has_prev)


def linear_bridge(values, observed):
    """Interior gaps -> linear interp between neighbours (edges untouched)."""
    T = values.shape[-1]
    idx = jnp.arange(T, dtype=jnp.float32)
    big = jnp.float32(1e30)
    # distance to previous / next observation via two locf passes
    fwd_v, fwd_has = _locf_scan(values, observed,
                                jnp.zeros(values.shape[:-1]),
                                jnp.zeros(values.shape[:-1], bool))
    fwd_i, _ = _locf_scan(jnp.broadcast_to(idx, values.shape), observed,
                          -jnp.ones(values.shape[:-1]),
                          jnp.zeros(values.shape[:-1], bool))
    rev = lambda x: jnp.flip(x, axis=-1)
    bwd_v, bwd_has = _locf_scan(rev(values), rev(observed),
                                jnp.zeros(values.shape[:-1]),
                                jnp.zeros(values.shape[:-1], bool))
    bwd_i, _ = _locf_scan(jnp.broadcast_to(idx, values.shape), rev(observed),
                          -jnp.ones(values.shape[:-1]),
                          jnp.zeros(values.shape[:-1], bool))
    bwd_v, bwd_has, bwd_i = rev(bwd_v), rev(bwd_has), (T - 1) - rev(bwd_i)
    span = jnp.maximum(bwd_i - fwd_i, 1e-6)
    frac = jnp.clip((idx - fwd_i) / span, 0.0, 1.0)
    interior = fwd_has & bwd_has
    interp = fwd_v + frac * (bwd_v - fwd_v)
    out = jnp.where(observed, values, jnp.where(interior, interp, fwd_v))
    return out, interior | fwd_has


def gap_fill(values, observed, state: GapFillState, tick_ts,
             strategy, *, tick_of_day=None, ewma_alpha: float = 0.2,
             use_pallas: bool = False):
    """Fill unobserved ticks. strategy: (S,) int32 index into STRATEGIES or a
    single string. Returns (filled_values, filled_mask, new_state).

    ``use_pallas`` only affects the string ``"locf"`` strategy (the other
    strategies and the per-stream int-vector form keep the XLA paths)."""
    E, S, T = values.shape
    if tick_of_day is None:
        tick_of_day = jnp.zeros((E, T), jnp.int32)

    # Strategy branches, computed lazily: a static (string) strategy only
    # pays for the branch it selects — the linear bridge alone costs four
    # extra associative scans, which matters inside the scan-fused engine
    # where gap-fill runs once per window on-device.
    def _locf():
        if use_pallas and isinstance(strategy, str) and strategy == "locf":
            from repro.kernels.locf.ops import locf as locf_kernel
            return locf_kernel(values, observed, state.last_value,
                               state.last_ts > -1e29)
        return locf(values, observed, state)

    def _linear():
        locf_v, locf_has = _locf()
        lin_v, lin_has = linear_bridge(values, observed)
        lin_v = jnp.where(observed | lin_has, lin_v, locf_v)
        return lin_v, lin_has | locf_has

    def _ewma():
        ew = state.ewma[..., None]
        ew_v = jnp.where(observed, values,
                         jnp.broadcast_to(ew, values.shape))
        ew_has = jnp.broadcast_to(state.last_ts[..., None] > -1e29,
                                  values.shape)
        return ew_v, ew_has

    def _seasonal():
        K = state.seasonal.shape[-1]
        sea = jnp.take_along_axis(
            state.seasonal, tick_of_day[:, None, :] % K, axis=-1)
        sea_n = jnp.take_along_axis(
            state.seasonal_n, tick_of_day[:, None, :] % K, axis=-1)
        return jnp.where(observed, values, sea), sea_n > 0

    branches = {"locf": _locf, "linear": _linear, "ewma": _ewma,
                "seasonal": _seasonal}
    if isinstance(strategy, str):
        out_v, out_h = branches[strategy]()
    else:
        stack_v, stack_h = map(jnp.stack, zip(*(branches[s]()
                                                for s in STRATEGIES)))
        sel = strategy[None, None, :, None]
        out_v = jnp.take_along_axis(stack_v, sel, axis=0)[0]
        out_h = jnp.take_along_axis(stack_h, sel, axis=0)[0]

    filled = (~observed) & out_h
    out = jnp.where(observed, values, jnp.where(filled, out_v, 0.0))

    # ---- state update (from OBSERVED ticks only) ----------------------------
    any_obs = observed.any(-1)
    big = jnp.float32(3.4e38)
    ts_b = jnp.broadcast_to(tick_ts[:, None, :], values.shape)
    last_key = jnp.where(observed, ts_b, -big)
    is_last = (last_key == last_key.max(-1, keepdims=True)) & observed
    new_last = f32.einsum("est,est->es", values,
                          is_last.astype(jnp.float32)) / \
        jnp.maximum(is_last.sum(-1), 1)
    new_last_ts = jnp.max(jnp.where(observed, ts_b, -1e30), axis=-1)
    obs_mean = f32.einsum("est,est->es", values, observed.astype(jnp.float32)) \
        / jnp.maximum(observed.sum(-1), 1)
    sea_mean, sea_n = _seasonal_update(state, values, observed, tick_of_day)
    new_state = GapFillState(
        last_value=jnp.where(any_obs, new_last, state.last_value),
        last_ts=jnp.maximum(state.last_ts, new_last_ts),
        ewma=jnp.where(any_obs,
                       (1 - ewma_alpha) * state.ewma + ewma_alpha * obs_mean,
                       state.ewma),
        seasonal=sea_mean,
        seasonal_n=sea_n,
    )
    return out, filled, new_state


def _seasonal_update(state, values, observed, tick_of_day):
    K = state.seasonal.shape[-1]
    oh = (jax.nn.one_hot(tick_of_day % K, K, dtype=jnp.float32)[:, None])  # (E,1,T,K)
    w = oh * observed[..., None]
    s = f32.einsum("est,estk->esk", values, w)
    # phrased as a dot: XLA:CPU's strided reduce of (E,S,T,K) over T is
    # ~6x slower than the equivalent contraction (see harmonize._harmonize_dense);
    # its 0/1 operands are exact in bfloat16, so default precision suffices
    n = jnp.einsum("est,estk->esk", jnp.ones_like(values), w)
    total_n = state.seasonal_n + n
    mean = jnp.where(total_n > 0,
                     (state.seasonal * state.seasonal_n + s) / jnp.maximum(total_n, 1),
                     state.seasonal)
    return mean, total_n
