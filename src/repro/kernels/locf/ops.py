"""Jit'd public wrapper for the LOCF kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.locf.kernel import LANES, locf_pallas
from repro.kernels.locf.ref import locf_ref


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def locf(values, observed, init_value, init_has, *, use_pallas: bool = True):
    """Batched entry: (E, S, T) + carry (E, S). Returns (filled, has)."""
    E, S, T = values.shape
    R = E * S
    if not use_pallas:
        out, has = locf_ref(values.reshape(R, T).astype(jnp.float32),
                            observed.reshape(R, T).astype(jnp.float32) > 0,
                            init_value.reshape(R).astype(jnp.float32),
                            init_has.reshape(R).astype(jnp.float32) > 0)
        return out.reshape(E, S, T), has.reshape(E, S, T)
    pad = (-R) % LANES

    def lanes(x, n):   # (E, S[, T]) -> (n, R + pad): rows onto lanes
        x = x.reshape(R, n).astype(jnp.float32).T
        return jnp.pad(x, ((0, 0), (0, pad))) if pad else x

    out, has = locf_pallas(lanes(values, T), lanes(observed, T),
                           lanes(init_value, 1), lanes(init_has, 1))
    unlanes = lambda x: x[:, :R].T.reshape(E, S, T)
    return unlanes(out), unlanes(has)
