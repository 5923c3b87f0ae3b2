"""Pallas TPU kernel: LOCF gap filling in one VMEM pass.

The XLA associative_scan materializes O(log T) full-size intermediates in
HBM; the kernel walks T once per tile with the carry in vregs, one
streaming read+write. Its wrapper (``ops.locf``) transposes the (E, S, T)
inputs to (T, E*S) before the kernel and its outputs back after it, so
the stage as a whole makes about two more passes over HBM.

Layout: rows (E*S) on the 128 lanes, ticks on sublanes. Each grid step
owns a (T, LANES) tile and reads/writes one tick row per loop step with a
dynamic sublane slice, which Mosaic lowers; a dynamic LANE index (one tick
column of an (R, T) tile) it does not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call

LANES = 128


def _kernel(values_ref, obs_ref, init_v_ref, init_h_ref, out_ref, has_ref):
    T = values_ref.shape[0]

    def body(t, carry):
        cv, ch = carry
        ot = obs_ref[pl.ds(t, 1), :] > 0
        cv = jnp.where(ot, values_ref[pl.ds(t, 1), :], cv)
        ch = jnp.where(ot, 1.0, ch)
        out_ref[pl.ds(t, 1), :] = cv
        has_ref[pl.ds(t, 1), :] = ch
        return cv, ch

    jax.lax.fori_loop(0, T, body, (init_v_ref[...], init_h_ref[...]))


def locf_pallas(values, observed, init_value, init_has):
    """values/observed: (T, R) f32; init_value/init_has: (1, R) f32.

    R % LANES == 0 (pad upstream). Returns (filled (T, R), has (T, R)).
    """
    T, R = values.shape
    assert R % LANES == 0, R
    tile = pl.BlockSpec((T, LANES), lambda i: (0, i))
    carry = pl.BlockSpec((1, LANES), lambda i: (0, i))
    out, has = pallas_call(
        _kernel,
        name="locf",
        grid=(R // LANES,),
        in_specs=[tile, tile, carry, carry],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((T, R), jnp.float32)] * 2,
    )(values, observed, init_value, init_has)
    return out, has > 0
