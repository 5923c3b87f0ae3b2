"""The JAX API seams this repository routes through one module.

The installed JAX is 0.9.0, and this module is written for it alone; it
holds no branch for another release. Call sites import the seams from
here instead of spelling them locally, so that the next JAX upgrade
changes one file:

  * ``make_mesh`` / ``abstract_mesh`` / ``set_mesh`` / ``shard_map`` —
    mesh construction (explicit Auto axis types), the ambient mesh and
    the shard_map entry point with its replication-check keyword.
  * ``cost_analysis`` — the compiled program's cost properties as one
    flat dict.
  * ``eval_jaxpr`` / ``source_summary`` / ``pallas_grid_mapping`` /
    ``pallas_block_sizes`` — the jaxpr and Pallas internals the contract
    checker (``repro.analysis.jaxpr_check``) reads.
  * ``jit_donated`` — ``jax.jit`` with buffer donation, absorbing the
    donation quirks below.
  * ``enable_compile_cache`` — the persistent compilation cache every
    entry point shares.
  * ``trace_annotation`` / ``trace_enabled`` — the profiler's host span
    and whether a profiler session records (``repro.runtime.spans``).

Donation quirk: some backends warn ("Some donated buffers were not
usable") instead of donating. ``jit_donated`` applies ``donate_argnums``
and silences that warning so benchmark output stays clean; donation is an
optimization, never a semantic requirement, in this repo.
"""
from __future__ import annotations

import os
import warnings
from pathlib import Path

import jax
from jax.sharding import AxisType

# <checkout>/.jax_cache: a fixed path (the cache key includes it), listed
# in .gitignore
_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``. Tests do not call this: a compile made for
    a described (unattached) chip is written to the cache but cannot be
    read back without one.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))


def trace_annotation(name: str, **metadata):
    """``jax.profiler.TraceAnnotation``: a named event, with ``metadata`` as
    its stats, on the trace's host plane while a profiler session records;
    about a microsecond to enter and leave while none does. Its
    ``set_metadata(**kw)`` adds stats before the exit."""
    return jax.profiler.TraceAnnotation(name, **metadata)


def trace_enabled() -> bool:
    """Whether a profiler session is recording host spans."""
    return jax.profiler.TraceAnnotation.is_enabled()


def make_mesh(devices, axis_names):
    """``jax.sharding.Mesh`` with explicit Auto axis types."""
    return jax.sharding.Mesh(devices, axis_names,
                             axis_types=(AxisType.Auto,) * len(axis_names))


def abstract_mesh(axis_sizes, axis_names):
    """``AbstractMesh`` from (sizes, names)."""
    return jax.sharding.AbstractMesh(tuple(int(s) for s in axis_sizes),
                                     tuple(axis_names))


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: every body here
    does collective-free per-rank work."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as one flat per-device dict."""
    return dict(compiled.cost_analysis() or {})


def eval_jaxpr(closed_jaxpr, *args):
    """Evaluate a ``ClosedJaxpr`` on concrete arguments."""
    return jax.core.eval_jaxpr(closed_jaxpr.jaxpr, closed_jaxpr.consts,
                               *args)


def source_summary(eqn) -> str:
    """``file:line (function)`` of the user frame that emitted ``eqn``."""
    from jax._src import source_info_util
    return str(source_info_util.summarize(eqn.source_info))


def pallas_grid_mapping(eqn):
    """The ``GridMapping`` of a ``pallas_call`` equation: ``grid``,
    ``num_inputs``/``num_outputs``, ``num_scratch_operands``,
    ``num_dynamic_grid_bounds`` and one ``block_mappings`` entry per
    input then output (each with ``index_map_jaxpr``; see
    :func:`pallas_block_sizes`)."""
    return eqn.params["grid_mapping"]


def pallas_block_sizes(block_mapping) -> tuple:
    """Per-dim block extents of one Pallas ``BlockMapping`` as ints.

    Entries are ``Blocked(block_size=n)`` (``Element`` and bounded
    slices carry a ``block_size`` too) or ``Squeezed()``, a size-1 dim
    the kernel does not see."""
    return tuple(int(getattr(b, "block_size", 1) or 1)
                 for b in block_mapping.block_shape)


def _dealias_donated(args, donate_argnums):
    """Copy duplicate buffers among donated arguments.

    XLA rejects donating the same underlying buffer twice, and zero-
    initialized pytrees (``init_state``) routinely alias their zero pages
    across leaves. Donation is an optimization, so the cheap fix is a copy
    of the duplicates, not an error surfaced to the caller.
    """
    import jax.numpy as jnp
    out = list(args)
    seen = set()
    for i in donate_argnums:
        if i >= len(out):
            continue
        leaves, treedef = jax.tree.flatten(out[i])
        fresh = []
        for x in leaves:
            if isinstance(x, jax.Array):
                try:
                    key = x.unsafe_buffer_pointer()
                except Exception:
                    key = id(x)
                if key in seen:
                    x = jnp.array(x, copy=True)
                else:
                    seen.add(key)
            fresh.append(x)
        out[i] = jax.tree.unflatten(treedef, fresh)
    return tuple(out)


def jit_donated(fn, donate_argnums=(), **jit_kwargs):
    """``jax.jit`` with ``donate_argnums``, absorbing donation quirks.

    Two backend quirks are handled here so call sites stay clean:
    duplicate-buffer donation (aliased zero pages in freshly initialized
    state pytrees) is de-aliased per call, and the "donated buffers were
    not usable" warning some backends emit instead of donating is
    silenced. When ``donate_argnums`` is empty this is exactly
    ``jax.jit(fn, **jit_kwargs)``.
    """
    if not donate_argnums:
        return jax.jit(fn, **jit_kwargs)
    donate_argnums = tuple(donate_argnums)
    jitted = jax.jit(fn, donate_argnums=donate_argnums, **jit_kwargs)

    def call(*args, **kwargs):
        args = _dealias_donated(args, donate_argnums)
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onated buffers.*")
            return jitted(*args, **kwargs)

    # keep lower/compile reachable for dry-run tooling
    call.lower = jitted.lower
    call._jitted = jitted
    return call
