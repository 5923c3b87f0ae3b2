"""Pallas TPU kernel: fused bucketize + per-tick aggregation.

The jnp path materializes an (R, M, T) one-hot in HBM (M raw samples x T
ticks per row) — at fleet scale that's the dominant harmonization traffic.
The kernel keeps the (T, LANES) accumulators in VMEM and streams the M
samples with a fori_loop, so the kernel reads only the (M, R) inputs and
writes the (T, R) outputs: arithmetic-intensity goes from O(1) to O(M)
per byte. Its wrapper (``ops.harmonize``) transposes the (E, S, M) inputs
to (M, E*S) and the outputs back to (E, S, T), about two more passes over
HBM outside the kernel.

Layout: rows (E*S) on the 128 lanes; samples and ticks on sublanes, so the
loop reads sample m with a dynamic sublane slice (Mosaic lowers no dynamic
lane index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call

LANES = 128


def _kernel(values_ref, ts_ref, valid_ref, t0_ref, out_ref, obs_ref, *,
            tick_s: float, n_ticks: int):
    M, L = values_ref.shape
    t0 = t0_ref[...]                                      # (1, L)
    tick = jax.lax.broadcasted_iota(jnp.int32, (n_ticks, L), 0)

    def body(m, carry):
        total, count = carry
        ts = ts_ref[pl.ds(m, 1), :]
        idx = jnp.ceil((ts - t0) / tick_s).astype(jnp.int32) - 1
        ok = valid_ref[pl.ds(m, 1), :] > 0
        h = ((tick == idx) & ok).astype(jnp.float32)      # (T, L)
        return total + h * values_ref[pl.ds(m, 1), :], count + h

    zero = jnp.zeros((n_ticks, L), jnp.float32)
    total, count = jax.lax.fori_loop(0, M, body, (zero, zero))
    observed = count > 0
    out_ref[...] = jnp.where(observed, total / jnp.maximum(count, 1.0), 0.0)
    obs_ref[...] = observed.astype(jnp.float32)


def harmonize_pallas(values, timestamps, valid, t0, *, tick_s: float,
                     n_ticks: int):
    """values/timestamps/valid: (M, R) f32; t0: (1, R). R % LANES == 0.

    Returns (tick means (T, R), observed (T, R))."""
    M, R = values.shape
    assert R % LANES == 0, R
    kern = functools.partial(_kernel, tick_s=tick_s, n_ticks=n_ticks)
    samples = pl.BlockSpec((M, LANES), lambda i: (0, i))
    ticks = pl.BlockSpec((n_ticks, LANES), lambda i: (0, i))
    out, obs = pallas_call(
        kern,
        name="harmonize",
        grid=(R // LANES,),
        in_specs=[samples, samples, samples,
                  pl.BlockSpec((1, LANES), lambda i: (0, i))],
        out_specs=[ticks, ticks],
        out_shape=[jax.ShapeDtypeStruct((n_ticks, R), jnp.float32)] * 2,
    )(values, timestamps, valid, t0)
    return out, obs > 0
