"""Pipelined (async double-buffered) scan engine + K/E autotuner.

``mode="scan_async"`` must be bit-identical to ``scan`` (and the sharded
composition to ``scan_sharded``): the pump thread performs exactly the
synchronous clock-advance/poll/drain sequence at the same window
boundaries, so the only per-window field allowed to differ is the wall
``latency_s`` metric. Also: prefetch-thread exceptions re-raise in the
Manager thread, and ``tune_scan_params`` is deterministic under a fixed
injected timer.
"""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core import PipelineConfig
from repro.core.autotune import (TuneResult, candidate_device_counts,
                                 tune_scan_params)
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.prefetch import WindowPrefetcher
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.system import PerceptaSystem, SourceSpec


def _system(mode, n_envs=2, scan_k=3, **kw):
    srcs = [
        SourceSpec("meter", "mqtt", SimulatedDevice("grid_kw", 60.0,
                                                    base=3.0, seed=1)),
        SourceSpec("price", "http", SimulatedDevice("price_eur", 300.0,
                                                    base=0.2, amplitude=0.05,
                                                    seed=2)),
    ]
    cfg = PipelineConfig(n_envs=n_envs, n_streams=2, n_ticks=8, tick_s=60.0,
                         max_samples=32)
    pred = Predictor(linear_policy(2, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     n_envs, cfg.n_features, replay_capacity=64)
    envs = [f"bldg-{i}" for i in range(n_envs)]
    return PerceptaSystem(envs, srcs, cfg, pred, speedup=5000.0,
                          manual_time=True, mode=mode, scan_k=scan_k, **kw)


def _strip(results):
    """Everything but the wall-clock latency metric must match exactly."""
    return [{k: v for k, v in r.items() if k != "latency_s"}
            for r in results]


# --------------------------------------------------------------------------
# Bit-identity: scan_async == scan == scan_sharded (+ the async composition)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("async_mode", ["scan_async", "scan_async_sharded"])
def test_scan_async_matches_scan_system(async_mode):
    # 7 windows over scan_k=3 -> two full batches + a partial one, so the
    # epoch handoff covers the ragged tail too
    ref = _strip(_system("scan").run_windows(7))
    ref_sharded = _strip(_system("scan_sharded").run_windows(7))
    sys_a = _system(async_mode)
    got = _strip(sys_a.run_windows(7))
    assert got == ref
    assert got == ref_sharded
    sys_a.stop()


@pytest.mark.parametrize("mode", ["scan_async", "scan_fused_decide_async"])
def test_async_staging_buffer_reused_only_after_consume(mode):
    """A staging buffer may be refilled only once the batch that last used
    it was consumed: ``jnp.asarray`` can alias host memory, so the device
    may still be reading it until then. A slow consumer lets the pump run
    as far ahead as the depth-1 ready buffer allows."""
    import time

    s = _system(mode)
    taken, consumed, early = [], [0], []
    stage, consume = s._staging_buffers, s._consume_batch

    def staging(K, E):
        bufs = stage(K, E)
        early.extend((len(taken), m) for m, b in enumerate(taken)
                     if b is bufs[0] and m >= consumed[0])
        taken.append(bufs[0])
        return bufs

    def slow_consume(pending):
        time.sleep(0.2)
        out = consume(pending)
        consumed[0] += 1
        return out

    s._staging_buffers, s._consume_batch = staging, slow_consume
    s.run_windows(8 * s.scan_k)
    s.stop()
    assert len(taken) == 8 and early == []


def test_scan_async_chained_calls_resume_epochs():
    """A second run_windows call reuses the pump thread and stays aligned."""
    a = _system("scan")
    b = _system("scan_async")
    ra = a.run_windows(3) + a.run_windows(4)
    rb = b.run_windows(3) + b.run_windows(4)
    assert [r["window"] for r in rb] == list(range(7))
    assert _strip(ra) == _strip(rb)
    # stats flow through the pump thread identically (same drain epochs)
    qa, qb = a.stats()["queues"], b.stats()["queues"]
    for env in qa:
        assert qa[env] == qb[env]
    b.stop()


_ASYNC_SHARDED_SCRIPT = """
import numpy as np
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.system import PerceptaSystem, SourceSpec
import jax
assert len(jax.devices()) == 8, jax.devices()

def mk(mode):
    srcs = [SourceSpec("meter", "mqtt",
                       SimulatedDevice("grid_kw", 60.0, base=3.0, seed=1)),
            SourceSpec("price", "http",
                       SimulatedDevice("price_eur", 300.0, base=0.2,
                                       amplitude=0.05, seed=2))]
    cfg = PipelineConfig(n_envs=8, n_streams=2, n_ticks=4, tick_s=60.0,
                         max_samples=16)
    pred = Predictor(linear_policy(2, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     8, cfg.n_features, replay_capacity=64)
    return PerceptaSystem([f"b{i}" for i in range(8)], srcs, cfg, pred,
                          speedup=5000.0, manual_time=True, mode=mode,
                          scan_k=3)

strip = lambda rs: [{k: v for k, v in r.items() if k != "latency_s"}
                    for r in rs]
ref = strip(mk("scan").run_windows(7))
sh = mk("scan_async_sharded")
assert dict(sh.pipeline.mesh.shape) == {"data": 8}, sh.pipeline.mesh
got = strip(sh.run_windows(7))
assert got == ref
sh.stop()
print("ASYNC_SHARDED_OK")
"""


def test_scan_async_sharded_multi_device_bit_identical():
    """Real 8-device forced CPU mesh in a subprocess (the XLA flag must
    precede JAX init): async + shard_map composition == plain scan."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _ASYNC_SHARDED_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ASYNC_SHARDED_OK" in out.stdout


# --------------------------------------------------------------------------
# Prefetcher: epoch protocol + exception propagation
# --------------------------------------------------------------------------

def test_prefetcher_exception_propagates_to_manager():
    calls = []

    def assemble(bounds, pump):
        calls.append(bounds)
        if len(calls) == 2:
            raise ValueError("drain exploded")
        return ("raw", list(bounds)), [0] * len(bounds)

    pf = WindowPrefetcher(assemble)
    pf.submit([(0.0, 1.0)])
    pf.submit([(1.0, 2.0)])
    first = pf.next_batch()
    assert first.epoch == 0 and first.counts == [0]
    with pytest.raises(ValueError, match="drain exploded"):
        pf.next_batch()
    # the prefetcher is poisoned afterwards: submitting again surfaces it
    with pytest.raises(RuntimeError):
        pf.submit([(2.0, 3.0)])
    pf.stop()


def test_prefetcher_epoch_order_and_backpressure():
    order = []
    gate = threading.Event()

    def assemble(bounds, pump):
        order.append(bounds[0][0])
        gate.wait(5.0)
        return ("raw", bounds[0][0]), [1]

    pf = WindowPrefetcher(assemble, depth=1)
    for j in range(4):
        pf.submit([(float(j), float(j) + 1.0)], pump=False)
    gate.set()
    got = [pf.next_batch() for _ in range(4)]
    assert [b.epoch for b in got] == [0, 1, 2, 3]
    assert order == [0.0, 1.0, 2.0, 3.0]     # strict plan order
    pf.stop()


def test_prefetcher_stop_with_abandoned_batches_and_restart():
    """A Manager that abandons its batches (consumer exception) must not
    wedge stop() on the full ready buffer, and a later submit() must start
    from a clean handoff state instead of replaying stale plans."""
    import time as _time

    assembled = []

    def assemble(bounds, pump):
        assembled.append(bounds[0][0])
        return ("raw", bounds[0][0]), [1]

    pf = WindowPrefetcher(assemble, depth=1)
    for j in range(4):          # never consumed: pump wedges on the buffer
        pf.submit([(float(j), float(j) + 1.0)], pump=False)
    t0 = _time.time()
    pf.stop()
    assert _time.time() - t0 < 5.0
    assert pf._thread is None
    # clean restart: fresh epochs, no stale plan ever re-assembled
    n_before = len(assembled)
    pf.submit([(100.0, 101.0)], pump=False)
    got = pf.next_batch()
    assert got.epoch == 0 and got.raw == ("raw", 100.0)
    assert assembled[n_before:] == [100.0]
    pf.stop()


def test_system_surfaces_pump_thread_failure(monkeypatch):
    sys_ = _system("scan_async")

    def boom(bounds):
        raise RuntimeError("accumulator corrupt")

    monkeypatch.setattr(sys_, "assemble_windows", boom)
    with pytest.raises(RuntimeError, match="accumulator corrupt"):
        sys_.run_windows(3)
    sys_.stop()


# --------------------------------------------------------------------------
# Autotuner: grid measurement, selection, determinism
# --------------------------------------------------------------------------

def _fake_measure(fn, *, k, n_devices, reps=3):
    """Deterministic synthetic timer: never executes fn, prefers K=4."""
    return {2: 0.004, 4: 0.006, 8: 0.020}[k] * n_devices


def test_autotuner_deterministic_under_fixed_measure():
    cfg = PipelineConfig(n_envs=2, n_streams=2, n_ticks=8, tick_s=60.0,
                        max_samples=32)
    a = tune_scan_params(cfg, k_grid=(2, 4, 8), device_counts=[1],
                         measure=_fake_measure)
    b = tune_scan_params(cfg, k_grid=(2, 4, 8), device_counts=[1],
                         measure=_fake_measure)
    assert a == b                       # identical TuneResult, grid included
    assert isinstance(a, TuneResult)
    # windows/s argmax of the synthetic grid: 4/0.006 > 8/0.020 > 2/0.004
    assert a.scan_k == 4 and a.mesh_devices == 1
    best = max(w for _, _, w in a.grid)
    assert a.best_windows_per_s == best


def test_autotuner_measures_real_dispatches():
    cfg = PipelineConfig(n_envs=2, n_streams=2, n_ticks=4, tick_s=60.0,
                         max_samples=16)
    res = tune_scan_params(cfg, k_grid=(2, 4), device_counts=[1], reps=1)
    assert {(k, n) for k, n, _ in res.grid} == {(2, 1), (4, 1)}
    assert all(w > 0 for _, _, w in res.grid)
    # selection is within 10% of the measured grid optimum (argmax => 0%)
    assert res.best_windows_per_s >= 0.9 * max(w for _, _, w in res.grid)


def test_candidate_device_counts_divisibility():
    assert candidate_device_counts(8, 8) == [1, 2, 4, 8]
    assert candidate_device_counts(6, 4) == [1, 2, 3]


def test_autotuner_floor_prunes_starved_mesh_splits():
    """Splits below min_envs_per_device never measure (an E=8 batch over 8
    devices is one env row per chip — pure dispatch overhead), and the
    skip is recorded on TuneResult.pruned."""
    calls = []

    def measure(fn, *, k, n_devices, reps=3):
        calls.append((k, n_devices))
        return 0.001 * k

    cfg = PipelineConfig(n_envs=8, n_streams=2, n_ticks=8, tick_s=60.0,
                         max_samples=32)
    res = tune_scan_params(cfg, k_grid=(2, 4), device_counts=[1, 4, 8],
                           measure=measure)
    assert all(n != 8 for _, n in calls)
    assert (None, 8, "envs_per_device<2") in res.pruned
    assert {n for _, n, _ in res.grid} == {1, 4}
    # the floor is a knob: relaxing it restores the split
    res2 = tune_scan_params(cfg, k_grid=(2,), device_counts=[1, 8],
                            measure=measure, min_envs_per_device=1)
    assert res2.pruned == () and {n for _, n, _ in res2.grid} == {1, 8}


def test_autotuner_early_stops_cells_far_off_incumbent():
    """A cell >prune_factor x slower than the incumbent stops the rest of
    its mesh-split column; selection stays deterministic under the
    injected timer (pruned set included)."""
    def measure(fn, *, k, n_devices, reps=3):
        if n_devices == 2:
            return 1.0          # 2 w/s at k=2: hopeless split
        return {2: 0.004, 4: 0.006}[k]

    cfg = PipelineConfig(n_envs=4, n_streams=2, n_ticks=8, tick_s=60.0,
                         max_samples=32)
    a = tune_scan_params(cfg, k_grid=(2, 4), device_counts=[1, 2],
                         measure=measure)
    b = tune_scan_params(cfg, k_grid=(2, 4), device_counts=[1, 2],
                         measure=measure)
    assert a == b
    # ndev=2 measured only at k=2; k=4 early-stopped
    assert {(k, n) for k, n, _ in a.grid} == {(2, 1), (4, 1), (2, 2)}
    assert a.pruned == ((4, 2, ">3x_off_incumbent"),)
    assert a.scan_k == 4 and a.mesh_devices == 1


def test_autotuner_fused_decide_grid_measures_fused_engine():
    """With decide=/decide_state= every cell runs the fused engine; the
    caller's decide state is never donated, so tuning leaves it intact."""
    import jax
    import numpy as np

    cfg = PipelineConfig(n_envs=2, n_streams=2, n_ticks=4, tick_s=60.0,
                         max_samples=16)
    pred = Predictor(linear_policy(2, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     2, cfg.n_features, replay_capacity=8)
    dstate = pred.decide_state()
    before = jax.tree.map(lambda x: np.asarray(x).copy(), dstate)
    res = tune_scan_params(cfg, k_grid=(2, 4), device_counts=[1], reps=1,
                           decide=pred.make_decide_fn(), decide_state=dstate)
    assert {(k, n) for k, n, _ in res.grid} == {(2, 1), (4, 1)}
    assert all(w > 0 for _, _, w in res.grid)
    for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(dstate)):
        assert (np.asarray(x) == np.asarray(y)).all()


def test_system_scan_k_auto_fused_decide_runs_tuned():
    """scan_k="auto" composes with the fused-decide mode end to end and
    the tuned system stays bit-identical to the scan reference."""
    sys_ = _system("scan_fused_decide", scan_k="auto",
                   autotune=dict(k_grid=(2, 4, 8), measure=_fake_measure))
    assert sys_.scan_k == 4
    ref = _strip(_system("scan", scan_k=4).run_windows(5))
    assert _strip(sys_.run_windows(5)) == ref
    sys_.stop()


def test_system_scan_k_auto_picks_measured_optimum():
    sys_ = _system("scan_async",
                   scan_k="auto",
                   autotune=dict(k_grid=(2, 4, 8), measure=_fake_measure))
    assert sys_.scan_k == 4
    assert sys_.tuned is not None and sys_.tuned.scan_k == 4
    # and the tuned system still runs, bit-identical to plain scan
    ref = _strip(_system("scan", scan_k=4).run_windows(5))
    assert _strip(sys_.run_windows(5)) == ref
    sys_.stop()
