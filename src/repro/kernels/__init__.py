"""Pallas kernels, each with a jitted entry (``ops.py``) and a pure-jnp
oracle (``ref.py``).

Every kernel is launched through :func:`pallas_call`, which takes the
interpret decision away from callers: it follows the backend the call is
lowered for.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` whose interpret mode follows the lowering backend.

    Lowered for CPU (the tests) the call runs the Pallas interpreter;
    lowered for TPU it is the compiled Mosaic kernel; any other backend
    fails to lower. ``jax.lax.platform_dependent`` stages both variants
    and lowering keeps only the target's branch, so an ahead-of-time
    compile for a described TPU builds the real kernel even in a process
    whose default backend is the CPU.
    """
    on_cpu = pl.pallas_call(kernel, interpret=True, **kwargs)
    on_tpu = pl.pallas_call(kernel, interpret=False, **kwargs)
    return lambda *args: jax.lax.platform_dependent(*args, cpu=on_cpu,
                                                    tpu=on_tpu)
