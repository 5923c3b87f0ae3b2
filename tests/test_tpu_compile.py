"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: an untiled block, a
dynamic lane index, a program that does not fit. These tests compile the
main path's Pallas kernels at the smoke deployment's widths
(``chip_smoke.py``: E=1024 environments x S=8 streams, T=60 ticks, M=64
samples, policy hidden 128) and the fused window->decide->bank scan step,
on one described chip and sharded on the 2x2 mesh, and check that each
kernel lowered to a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every pytest worker imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.core import PipelineConfig
from repro.core.frame import RawWindow
from repro.core.pipeline import (init_state, make_run_many_decide_sharded,
                                 run_many_decide)
from repro.core.reward import energy_reward_spec
from repro.distribution import sharding
from repro.kernels.harmonize.ops import harmonize
from repro.kernels.locf.ops import locf
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.window_agg.ops import window_agg
from repro.runtime.policies import PolicyConfig
from repro.runtime.predictor import ActionSpace, Predictor

E, S, T, M, K, A, H = 1024, 8, 60, 64, 8, 4, 128


@pytest.fixture(scope="module")
def topo():
    import importlib.util
    import os
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # the compiler is there: this must not skip
        pytest.fail(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _custom_calls(text: str) -> set:
    """Kernel names that appear as tpu_custom_call instructions."""
    names = ("locf", "window_agg", "rglru_scan", "harmonize")
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    return {n for n in names if any(f"%{n}" in ln for ln in lines)}


_KERNELS = {
    "locf": (functools.partial(locf, use_pallas=True),
             [((E, S, T), jnp.float32), ((E, S, T), jnp.bool_),
              ((E, S), jnp.float32), ((E, S), jnp.bool_)]),
    "window_agg": (functools.partial(window_agg, use_pallas=True),
                   [((E, S, T), jnp.float32), ((E, S, T), jnp.bool_),
                    ((E, S), jnp.float32), ((E, S), jnp.float32)]),
    "rglru_scan": (functools.partial(rglru_scan, use_pallas=True),
                   [((E, 1, H), jnp.float32), ((E, 1, H), jnp.float32),
                    ((E, H), jnp.float32)]),
    "harmonize": (functools.partial(harmonize, tick_s=60.0, n_ticks=T,
                                    use_pallas=True),
                  [((E, S, M), jnp.float32), ((E, S, M), jnp.float32),
                   ((E, S, M), jnp.bool_), ((E,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_compiles_for_tpu(name, one_chip):
    fn, shapes = _KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert _custom_calls(text) == {name}


def _deployment(policy, use_pallas, n_envs=E, capacity=256):
    """The smoke deployment's config, decide fns and decide carry (host
    arrays; only their shapes are compiled)."""
    cfg = PipelineConfig(n_envs=n_envs, n_streams=S, n_ticks=T,
                         tick_s=60.0, max_samples=M, use_pallas=use_pallas,
                         feature_agg="mean" if use_pallas else "last")
    pred = Predictor(policy, energy_reward_spec(price_idx=1, grid_idx=0,
                                                temp_idx=2),
                     ActionSpace(-np.ones(A), np.ones(A)), n_envs,
                     cfg.n_features, replay_capacity=capacity)
    return cfg, pred.make_decide_fn(), pred.decide_state()


def _batch_shapes(n_envs):
    sds = lambda dt: jax.ShapeDtypeStruct((K, n_envs, S, M), dt)
    return (RawWindow(sds(jnp.float32), sds(jnp.float32), sds(jnp.bool_)),
            jax.ShapeDtypeStruct((K, n_envs), jnp.float32))


def test_fused_decide_step_compiles_one_chip(one_chip):
    """The whole window->decide->bank scan with every main-path kernel on:
    locf gap-fill, window_agg features and the rglru policy update."""
    cfg, decide, dstate = _deployment(
        PolicyConfig("rglru", {"hidden": H, "use_pallas": True}), True)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    args = jax.tree.map(place, (jax.eval_shape(lambda: init_state(cfg)),
                                dstate) + _batch_shapes(E))
    fn = jax.jit(functools.partial(run_many_decide, cfg, decide))
    text = fn.lower(*args).compile().as_text()
    assert _custom_calls(text) == {"locf", "window_agg", "rglru_scan"}


def test_fused_decide_step_compiles_sharded_2x2(topo):
    """The env-sharded engine on a {data: 4} mesh of described chips: it
    compiles collective-free, and each chip holds a quarter of the replay
    ring."""
    cfg, decide, dstate = _deployment(PolicyConfig("mlp", {"hidden": H}),
                                      False, capacity=1024)
    mesh = sharding.env_mesh(E, devices=topo.devices)
    assert dict(mesh.shape) == {"data": 4}
    fn, _ = make_run_many_decide_sharded(cfg, decide, dstate, mesh)

    def place(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: hasattr(x, "ndim"))

    state = jax.eval_shape(lambda: init_state(cfg))
    raw, starts = _batch_shapes(E)
    args = (place(state, sharding.env_specs(state, 0)),
            place(dstate, sharding.decide_specs(dstate, 0)),
            place(raw, sharding.env_specs(raw, 1)),
            place(starts, sharding.env_specs(starts, 1)))
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "collective-permute",
               "all-to-all"):
        assert op not in text, op
    ring = sum(np.asarray(x).nbytes for x in jax.tree.leaves(dstate.replay))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert ring / 4 < per_device < ring / 2
