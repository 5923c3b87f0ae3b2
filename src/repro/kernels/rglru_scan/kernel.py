"""Pallas TPU kernel: RG-LRU linear recurrence h_t = a_t h_{t-1} + b_t.

The recurrence is sequential in T but perfectly parallel over (batch,
channel). Tiling: grid (B, W/128) — each kernel instance owns a (T, 128)
channel stripe in VMEM and walks T with a fori_loop, reading and writing
one sublane row per step, so HBM sees a single streaming read of a/b and
write of h (the XLA associative_scan path materializes O(log T)
intermediate full-size arrays instead). The carry-in and carry-out travel
as (B, 1, W) so their blocks' last two dims (1, 128) are full-dim and
lane-aligned — a (1, 128) block over a (B, W) array is not a legal TPU
tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call

LANES = 128


def _kernel(a_ref, b_ref, h0_ref, out_ref, hlast_ref):
    T = a_ref.shape[1]

    def body(t, h):
        h = a_ref[0, pl.ds(t, 1), :] * h + b_ref[0, pl.ds(t, 1), :]
        out_ref[0, pl.ds(t, 1), :] = h
        return h

    hlast_ref[0] = jax.lax.fori_loop(0, T, body, h0_ref[0])


def rglru_scan_pallas(a, b, h0):
    """a, b: (B, T, W) f32; h0: (B, 1, W). W % 128 == 0 (pad upstream).

    Returns (hs (B, T, W), h_last (B, 1, W))."""
    B, T, W = a.shape
    assert W % LANES == 0, W
    seq = pl.BlockSpec((1, T, LANES), lambda bi, wi: (bi, 0, wi))
    row = pl.BlockSpec((1, 1, LANES), lambda bi, wi: (bi, 0, wi))
    return pallas_call(
        _kernel,
        name="rglru_scan",
        grid=(B, W // LANES),
        in_specs=[seq, seq, row],
        out_specs=[seq, row],
        out_shape=[jax.ShapeDtypeStruct((B, T, W), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, W), jnp.float32)],
    )(a, b, h0)
