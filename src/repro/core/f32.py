"""Float32 contractions at float32 precision on every backend.

The window body writes many of its sums as contractions: a masked mean is
a dot of the values with the mask, a bucket total a dot with a one-hot, a
LOCF select a dot with a one-hot too. Their operands are float32 and the
pipeline's results are specified in float32. XLA:CPU computes them so; the
TPU's default matmul precision instead rounds each float32 operand to
bfloat16 (8 significant bits), which on a v5e moved served rewards by
~6e-4 relative (PERF.md, "Findings"). Every such contraction over values
goes through :func:`einsum`, which asks for ``Precision.HIGHEST`` —
float32 products — and changes nothing on the CPU. Counts (contractions
of 0/1 operands, exact in bfloat16) stay at default precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def einsum(subscripts: str, *operands):
    """``jnp.einsum`` with float32-exact operand precision."""
    return jnp.einsum(subscripts, *operands,
                      precision=jax.lax.Precision.HIGHEST)
