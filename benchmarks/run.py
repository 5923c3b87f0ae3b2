"""Benchmark harness — one function per paper table/figure.

Percepta's paper defers benchmarking to future work but enumerates the plan
(§V): network I/O under load, CPU/memory across stress levels, performance
across deployment strategies. Each bench below implements one of those
tables (plus serving, kernels, and the dry-run roofline summary).

Prints ``name,us_per_call,derived`` CSV rows (CPU wall time; the TPU-target
numbers live in the roofline table from the dry-run artifacts). ``--json
PATH`` additionally writes every row plus the windows/s / records/s
summary (per execution mode and ingest path) as machine-readable JSON so
the perf trajectory is tracked across PRs (``BENCH_pr2.json``).

``--host-devices N`` forces an N-device CPU mesh
(``--xla_force_host_platform_device_count``) so the ``scan_sharded``
shard_map path is exercised without real multi-chip hardware; it must run
before JAX initializes, which is why every bench imports jax lazily.

Run: ``PYTHONPATH=src python -m benchmarks.run [--quick]
[--host-devices 8] [--json BENCH_pr2.json]``
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

RESULTS: list = []                    # every _row, for --json
SUMMARY: dict = {"windows_per_s": {}, "records_per_s": {}}


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)
    RESULTS.append({"name": name, "us_per_call": round(us, 1),
                    "derived": derived})


def _subprocess_env(xla_flags: str) -> dict:
    """Environment for an acceptance-cell subprocess: fresh XLA flags, the
    CPU backend, and this repo's src/ ahead of any inherited PYTHONPATH
    entries. These cells emulate an accelerator on the host CPU by their
    own XLA flags, and the parent process already holds the accelerator
    (one process per chip), so a child must never reach for it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = xla_flags
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    return env


def _time(fn, n=5, warmup=2, best=False):
    """Mean (default) or best-of-n microseconds per call.

    ``best=True`` reports the fastest rep — the robust estimator when the
    measured quantity is a dispatch-overhead ratio and the box is shared
    (one preempted rep poisons a mean but not a min).
    """
    for _ in range(warmup):
        fn()
    if best:
        out = float("inf")
        for _ in range(n):
            t0 = time.time()
            fn()
            out = min(out, time.time() - t0)
        return out * 1e6
    t0 = time.time()
    for _ in range(n):
        fn()
    return (time.time() - t0) / n * 1e6


# --------------------------------------------------------------------------
# Table 1 — ingest/network-I/O throughput under varying load
# --------------------------------------------------------------------------

def bench_ingest(quick=False):
    from repro.runtime.queues import QueueBroker
    from repro.runtime.records import CODECS
    from repro.runtime.translator import Translator

    for proto in ("mqtt", "http", "amqp"):
        enc, _ = CODECS[proto]
        tr = Translator("src", proto)
        broker = QueueBroker()
        n = 2_000 if quick else 20_000
        payloads = [enc("s", float(i), float(i) * 0.5) for i in range(n)]

        def run():
            for i, p in enumerate(payloads):
                rec = tr.translate(f"env-{i % 16}", p)
                broker.publish(rec)

        t0 = time.time()
        run()
        dt = time.time() - t0
        _row(f"ingest_{proto}", dt / n * 1e6, f"{n / dt:.0f} msg/s")


# --------------------------------------------------------------------------
# Table 2 — per-tick pipeline latency: modular vs fused vs scan (3 axes)
# --------------------------------------------------------------------------

def _pipeline(E, S=8, T=16, M=64, mode="fused", K=1):
    import jax.numpy as jnp

    from repro.core import PerceptaPipeline, PipelineConfig
    from repro.core.frame import make_raw_window

    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                         max_samples=M)
    pipe = PerceptaPipeline(cfg, mode=mode,
                            donate=mode in ("scan", "scan_sharded"))
    state = pipe.init_state()
    rng = np.random.RandomState(0)
    if mode in ("scan", "scan_sharded"):
        raws = make_raw_window(
            rng.normal(5, 2, (K, E, S, M)).astype(np.float32),
            rng.uniform(0, T * 60, (K, E, S, M)).astype(np.float32),
            rng.rand(K, E, S, M) > 0.3)
        ws = jnp.zeros((K, E), jnp.float32)

        def run():
            nonlocal state
            state, feats, frames = pipe.run_many(state, raws, ws)
            feats.features.block_until_ready()

        return run

    raw = make_raw_window(rng.normal(5, 2, (E, S, M)).astype(np.float32),
                          rng.uniform(0, T * 60, (E, S, M)).astype(np.float32),
                          rng.rand(E, S, M) > 0.3)
    ws = jnp.zeros((E,), jnp.float32)

    def run():
        nonlocal state
        state, feats, frame = pipe.run_tick(state, raw, ws)
        feats.features.block_until_ready()

    return run


def bench_tick_latency(quick=False):
    import jax
    envs = (16, 256) if quick else (16, 256, 1024)
    K = 8 if quick else 16
    ndev = len(jax.devices())
    for E in envs:
        t_mod = _time(_pipeline(E, mode="modular"), n=3 if quick else 8)
        t_fus = _time(_pipeline(E, mode="fused"), n=3 if quick else 8)
        t_scan = _time(_pipeline(E, mode="scan", K=K),
                       n=3 if quick else 8) / K  # per-tick, one dispatch per K
        _row(f"tick_modular_E{E}", t_mod, "paper-faithful per-module jits")
        _row(f"tick_fused_E{E}", t_fus,
             f"speedup {t_mod / t_fus:.2f}x over modular")
        _row(f"tick_scan_E{E}", t_scan,
             f"K={K} windows/dispatch | speedup {t_fus / t_scan:.2f}x over "
             f"fused | {1e6 / t_scan:.0f} windows/s")
        # fourth measured axis: the same scan under shard_map, envs sharded
        t_shard = _time(_pipeline(E, mode="scan_sharded", K=K),
                        n=3 if quick else 8) / K
        _row(f"tick_scan_sharded_E{E}", t_shard,
             f"K={K} | {ndev}-device mesh | "
             f"{t_scan / t_shard:.2f}x vs scan | "
             f"{1e6 / t_shard:.0f} windows/s")


# --------------------------------------------------------------------------
# Table 2b — scan engine acceptance cell: K=32 windows, E=8 envs, S=8 streams
# --------------------------------------------------------------------------

def bench_scan_engine(quick=False):
    import jax
    import jax.numpy as jnp

    from repro.core import PerceptaPipeline, PipelineConfig
    from repro.core.frame import RawWindow, make_raw_window

    K, E, S, T, M = 32, 8, 8, 16, 64
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                         max_samples=M)
    rng = np.random.RandomState(0)
    raws = make_raw_window(
        rng.normal(5, 2, (K, E, S, M)).astype(np.float32),
        (rng.uniform(0, T * 60, (K, E, S, M))
         + np.arange(K)[:, None, None, None] * T * 60).astype(np.float32),
        rng.rand(K, E, S, M) > 0.3)
    starts = jnp.asarray(np.arange(K, dtype=np.float32)[:, None] * (T * 60.0)
                         * np.ones((1, E), np.float32))
    per_window = [RawWindow(raws.values[k], raws.timestamps[k], raws.valid[k])
                  for k in range(K)]

    fused = PerceptaPipeline(cfg, mode="fused")
    scan = PerceptaPipeline(cfg, mode="scan")
    state0 = fused.init_state()

    # correctness: scan must match K sequential fused ticks bit-for-bit
    s = state0
    seq_feats = []
    for k in range(K):
        s, f, _ = fused.run_tick(s, per_window[k], starts[k])
        seq_feats.append(np.asarray(f.features))
    _, feats, _ = scan.run_many(state0, raws, starts)
    err = float(np.max(np.abs(np.asarray(feats.features)
                              - np.stack(seq_feats))))

    def run_seq():
        st = state0
        for k in range(K):
            st, f, _ = fused.run_tick(st, per_window[k], starts[k])
        f.features.block_until_ready()

    def run_scan():
        st, f, _ = scan.run_many(state0, raws, starts)
        f.features.block_until_ready()

    n = 6 if quick else 12
    t_seq = _time(run_seq, n=n, best=True)
    t_scan = _time(run_scan, n=n, best=True)
    wps_seq = K / (t_seq / 1e6)
    wps_scan = K / (t_scan / 1e6)
    SUMMARY["windows_per_s"]["fused_seq"] = round(wps_seq, 1)
    SUMMARY["windows_per_s"]["scan"] = round(wps_scan, 1)
    _row(f"scan_fused_seq_K{K}_E{E}_S{S}", t_seq / K,
         f"{wps_seq:.0f} windows/s ({K} dispatches)")
    _row(f"scan_engine_K{K}_E{E}_S{S}", t_scan / K,
         f"{wps_scan:.0f} windows/s (1 dispatch) | "
         f"speedup {wps_scan / wps_seq:.2f}x | max_abs_err {err:.2e}")


# --------------------------------------------------------------------------
# Table 2c — env-sharded scan engine: same cell under shard_map on the mesh
# --------------------------------------------------------------------------

def bench_scan_sharded(quick=False):
    import jax
    import jax.numpy as jnp

    from repro.core import PerceptaPipeline, PipelineConfig
    from repro.core.frame import make_raw_window

    K, E, S, T, M = 32, 8, 8, 16, 64
    ndev = len(jax.devices())
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                         max_samples=M)
    rng = np.random.RandomState(0)
    raws = make_raw_window(
        rng.normal(5, 2, (K, E, S, M)).astype(np.float32),
        (rng.uniform(0, T * 60, (K, E, S, M))
         + np.arange(K)[:, None, None, None] * T * 60).astype(np.float32),
        rng.rand(K, E, S, M) > 0.3)
    starts = jnp.asarray(np.arange(K, dtype=np.float32)[:, None] * (T * 60.0)
                         * np.ones((1, E), np.float32))
    scan = PerceptaPipeline(cfg, mode="scan")
    shard = PerceptaPipeline(cfg, mode="scan_sharded")
    state0 = scan.init_state()

    # acceptance: sharded outputs bit-identical to the single-device scan
    _, f_ref, _ = scan.run_many(state0, raws, starts)
    _, f_sh, _ = shard.run_many(state0, raws, starts)
    err = float(np.max(np.abs(np.asarray(f_ref.features)
                              - np.asarray(f_sh.features))))

    def run_scan():
        st, f, _ = scan.run_many(state0, raws, starts)
        f.features.block_until_ready()

    def run_shard():
        st, f, _ = shard.run_many(state0, raws, starts)
        f.features.block_until_ready()

    n = 6 if quick else 12
    t_scan = _time(run_scan, n=n, best=True)
    t_shard = _time(run_shard, n=n, best=True)
    wps = K / (t_shard / 1e6)
    mesh_n = int(np.prod(list(shard.mesh.shape.values())))
    SUMMARY["windows_per_s"]["scan_sharded"] = round(wps, 1)
    SUMMARY["scan_sharded_max_abs_err"] = err
    SUMMARY["mesh_devices"] = mesh_n
    _row(f"scan_sharded_K{K}_E{E}_S{S}", t_shard / K,
         f"{wps:.0f} windows/s | {mesh_n}-device env mesh ({ndev} visible) | "
         f"{t_scan / t_shard:.2f}x vs scan | max_abs_err {err:.2e}")


# --------------------------------------------------------------------------
# Table 2d — pipelined (async double-buffered) scan engine + K/E autotuner
# --------------------------------------------------------------------------

# The overlap cell runs in a SUBPROCESS with
# ``--xla_cpu_multi_thread_eigen=false``: on a small CI box, XLA:CPU's
# contraction threadpool otherwise saturates every core during the device
# phase, so there is no spare capacity for host/device overlap to reclaim —
# the flag emulates the deployment this engine targets (an accelerator that
# does not consume host CPU) without perturbing any other cell's flags.
# The measurement itself is drift-immune: scan and scan_async reps are
# interleaved in PAIRS and the reported speedup is the MEDIAN of per-pair
# ratios, because shared-box throughput drifts ~2x on minute timescales,
# which corrupts best-of comparisons taken seconds apart.
_ASYNC_CELL_SCRIPT = """
import json, time
import numpy as np
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.records import RecordBatch
from repro.runtime.system import PerceptaSystem, SourceSpec
import jax

E, S, K, M = 8, 8, 32, 64
T, TICK_S, PER = 64, 15.0, 160   # device-heavy tick math + dense ingest

def mk(mode):
    srcs = [SourceSpec(f"s{i}", "mqtt",
                       SimulatedDevice(f"st{i}", 60.0, base=3.0, seed=i))
            for i in range(S)]
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=TICK_S,
                         max_samples=M, harmonize_method="onehot",
                         gap_strategy="linear")
    pred = Predictor(linear_policy(S, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     E, cfg.n_features, replay_capacity=64)
    return PerceptaSystem([f"b{i}" for i in range(E)], srcs, cfg, pred,
                          speedup=1e9, manual_time=True, mode=mode,
                          scan_k=K)

def publish(s, n_windows, rng):
    # a loaded broker: per-poll RecordBatch columns already queued, the
    # shape a real RabbitMQ consumer sees under sustained inbound load.
    # Anchored at the system's CURRENT window so repeated reps keep every
    # window fully populated (records behind the clock would be stale).
    w = s.window_s
    n = n_windows * PER
    t0 = s.window_bounds(s.window_index)[0]
    for env in s.env_ids:
        for src in s.sources:
            ts = np.sort(rng.uniform(t0, t0 + n_windows * w, n))
            s.broker.publish(RecordBatch.from_columns(
                env, src.device.stream, ts, rng.normal(5, 2, n)))

QUICK = __QUICK__
N = 96
PAIRS = 8 if QUICK else 12  # first pair is jit/cache warmup, discarded


def parallel_factor():
    # self-calibration: how much extra CPU a second worker actually buys on
    # this host (2.0 = two real cores; ~1.3 = one core + SMT sibling). The
    # overlap speedup is physically bounded by this number, so record it
    # next to the measurement.
    import multiprocessing as mp

    def burn(dur, q):
        t0 = time.time()
        n = 0
        while time.time() - t0 < dur:
            for _ in range(10000):
                n += 1
        q.put(n)

    q = mp.Queue()
    p = mp.Process(target=burn, args=(1.5, q))
    t0 = time.time(); p.start(); p.join()
    r1 = q.get() / (time.time() - t0)
    q = mp.Queue()
    ps = [mp.Process(target=burn, args=(1.5, q)) for _ in range(2)]
    t0 = time.time()
    for p in ps:
        p.start()
    for p in ps:
        p.join()
    r2 = sum(q.get() for _ in ps) / (time.time() - t0)
    return r2 / r1


ss, sa = mk("scan"), mk("scan_async")
ss.run_windows(K, pump=False)
sa.run_windows(K, pump=False)

# host-assembly share of scan wall time (phase decomposition on the twin)
publish(ss, N, np.random.RandomState(0))
A = D = C = 0.0
for b in range(N // K):
    bounds = [ss.window_bounds(ss.window_index + j) for j in range(K)]
    t0 = time.time(); raw, counts = ss.assemble_windows(bounds)
    A += time.time() - t0
    t0 = time.time()
    feats, frames, td = ss._dispatch_scan(raw, K)
    jax.block_until_ready(feats.features)
    D += time.time() - t0
    t0 = time.time(); ss._consume_scan(bounds, counts, feats, frames, td)
    C += time.time() - t0

ratios, tot_s, tot_a, best_s, best_a = [], 0.0, 0.0, 0.0, 0.0
for pair in range(PAIRS):
    publish(ss, N, np.random.RandomState(0))
    t0 = time.time(); ss.run_windows(N, pump=False); dt_s = time.time() - t0
    publish(sa, N, np.random.RandomState(0))
    t0 = time.time(); sa.run_windows(N, pump=False); dt_a = time.time() - t0
    if pair == 0:
        continue    # warmup pair: first-touch caches, thread spin-up
    ratios.append(dt_s / dt_a)
    tot_s += dt_s
    tot_a += dt_a
    best_s = max(best_s, N / dt_s)
    best_a = max(best_a, N / dt_a)
sa.stop(); ss.stop()
print(json.dumps({
    "windows_per_s_scan": round(best_s, 1),
    "windows_per_s_scan_async": round(best_a, 1),
    # ratio of interleaved totals: per-leg box noise (shared-host bursts)
    # cancels in expectation across many alternated short legs
    "speedup": round(tot_s / tot_a, 2),
    "speedup_median_of_pairs": round(float(np.median(ratios)), 2),
    "pair_ratios": [round(r, 2) for r in ratios],
    # what perfect overlap of these phases would yield...
    "ideal_speedup": round((A + D + C) / (max(A, D) + C), 2),
    # ...and the host's real concurrency budget bounding it (2.0 = two
    # full cores; ~1.3 = one physical core + SMT sibling)
    "host_parallel_factor": round(parallel_factor(), 2),
    "host_assembly_frac": round(A / (A + D + C), 2),
    # total host-side share (assembly + consume) of scan wall — the part
    # of the loop the device cannot hide; PR 4's batched Predictor consume
    # attacks the C term (see bench_predictor_batch for before/after)
    "host_share": round((A + C) / (A + D + C), 2),
    "scan_phase_ms": {"assemble": round(A / (N // K) * 1e3, 1),
                      "device": round(D / (N // K) * 1e3, 1),
                      "consume": round(C / (N // K) * 1e3, 1)},
    "cell": {"K": K, "E": E, "S": S, "T": T, "M": M,
             "records_per_stream_window": PER},
}))
"""


def bench_scan_async(quick=False):
    import subprocess

    from repro.core import PipelineConfig
    from repro.core.reward import energy_reward_spec
    from repro.runtime.predictor import (ActionSpace, Predictor,
                                         linear_policy)
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import PerceptaSystem, SourceSpec

    # --- acceptance: bit-identical to scan on the K=32/E=8/S=8 cell -------
    def mk(mode):
        srcs = [SourceSpec(f"s{i}", "mqtt",
                           SimulatedDevice(f"st{i}", 60.0, base=3.0, seed=i))
                for i in range(8)]
        cfg = PipelineConfig(n_envs=8, n_streams=8, n_ticks=16, tick_s=60.0,
                             max_samples=64)
        pred = Predictor(
            linear_policy(8, 2),
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            8, cfg.n_features, replay_capacity=64)
        return PerceptaSystem([f"b{i}" for i in range(8)], srcs, cfg, pred,
                              speedup=1e9, manual_time=True, mode=mode,
                              scan_k=32)

    n = 32 if quick else 64
    strip = lambda rs: [{k: v for k, v in r.items() if k != "latency_s"}
                        for r in rs]
    sa = mk("scan_async")
    ident = strip(mk("scan").run_windows(n)) == strip(sa.run_windows(n))
    sa.stop()
    SUMMARY["scan_async_bit_identical"] = bool(ident)
    _row("scan_async_identity_K32_E8_S8", 0.0,
         f"bit_identical {ident} over {n} windows")

    # --- overlap cell (subprocess; see _ASYNC_CELL_SCRIPT header) ---------
    env = _subprocess_env("--xla_cpu_multi_thread_eigen=false")
    script = _ASYNC_CELL_SCRIPT.replace("__QUICK__", str(bool(quick)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    cell = json.loads(out.stdout.strip().splitlines()[-1])
    # schema rule: every number appears ONCE — the overlap cell's
    # windows/s live inside the nested scan_async block only (they used to
    # be duplicated under the top-level windows_per_s map, which made
    # artifact diffs double-count them)
    SUMMARY["scan_async"] = cell
    ph = cell["scan_phase_ms"]
    _row("scan_async_overlap_K32_E8_S8_T64",
         1e6 / cell["windows_per_s_scan_async"],
         f"{cell['windows_per_s_scan_async']:.0f} windows/s | "
         f"{cell['speedup']:.2f}x vs scan "
         f"({len(cell['pair_ratios'])} interleaved pairs, ratio of totals; "
         f"median {cell['speedup_median_of_pairs']:.2f}x, ideal "
         f"{cell['ideal_speedup']:.2f}x, host parallel factor "
         f"{cell['host_parallel_factor']:.2f}) | "
         f"host assembly {cell['host_assembly_frac']:.0%} / host total "
         f"{cell['host_share']:.0%} of scan wall "
         f"(A {ph['assemble']:.0f} / D {ph['device']:.0f} / "
         f"C {ph['consume']:.0f} ms/batch)")


# --------------------------------------------------------------------------
# Table 2e — batched Predictor consume: on_windows vs per-window on_tick
# --------------------------------------------------------------------------

# Before/after phase decomposition of the PR 3 overlap cell under the same
# accelerator-emulating XLA flag: twin scan systems consume identical
# batches, one through the per-window on_tick reference loop, one through
# the single-dispatch on_windows scan. Reported: A/D/C phase times, the
# host share (A+C)/(A+D+C) both ways, and bit-identity of every output row
# + the replay ring across the two consume paths.
_PRED_BATCH_SCRIPT = """
import json, time
import numpy as np
import jax
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.records import RecordBatch
from repro.runtime.system import PerceptaSystem, SourceSpec

E, S, K, M = 8, 8, 32, 64
T, TICK_S, PER = 64, 15.0, 160

def mk(batched):
    srcs = [SourceSpec(f"s{i}", "mqtt",
                       SimulatedDevice(f"st{i}", 60.0, base=3.0, seed=i))
            for i in range(S)]
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=TICK_S,
                         max_samples=M, harmonize_method="onehot",
                         gap_strategy="linear")
    pred = Predictor(linear_policy(S, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     E, cfg.n_features, replay_capacity=64)
    return PerceptaSystem([f"b{i}" for i in range(E)], srcs, cfg, pred,
                          speedup=1e9, manual_time=True, mode="scan",
                          scan_k=K, batched_consume=batched)

def publish(s, n_windows, rng):
    w = s.window_s
    n = n_windows * PER
    t0 = s.window_bounds(s.window_index)[0]
    for env in s.env_ids:
        for src in s.sources:
            ts = np.sort(rng.uniform(t0, t0 + n_windows * w, n))
            s.broker.publish(RecordBatch.from_columns(
                env, src.device.stream, ts, rng.normal(5, 2, n)))

QUICK = __QUICK__
N = 64 if QUICK else 96
REPS = 2 if QUICK else 3

def measure(s, rows):
    A = D = C = 0.0
    for b in range(N // K):
        bounds = [s.window_bounds(s.window_index + j) for j in range(K)]
        t0 = time.time(); raw, counts = s.assemble_windows(bounds)
        A += time.time() - t0
        t0 = time.time()
        feats, frames, td = s._dispatch_scan(raw, K)
        jax.block_until_ready(feats.features)
        D += time.time() - t0
        t0 = time.time()
        out = s._consume_scan(bounds, counts, feats, frames, td)
        C += time.time() - t0
        rows.extend({k: v for k, v in r.items() if k != "latency_s"}
                    for r in out)
    return A, D, C

# Interleaved legs + pooled A/D: the assemble and dispatch phases run
# IDENTICAL code on both twins (only the consume path differs), so their
# best-of is taken across both twins' legs — shared-box drift between
# sequentially-measured twins would otherwise pollute the share deltas.
sys_by = {"perwindow": mk(False), "batched": mk(True)}
rows_by = {}
legs = {"perwindow": [], "batched": []}
for s in sys_by.values():
    s.run_windows(K, pump=False)                 # jit/cache warmup
for rep in range(REPS):                          # identical publish seeds
    for name, s in sys_by.items():
        publish(s, N, np.random.RandomState(rep))
        rows = []
        legs[name].append(measure(s, rows))
        rows_by[name] = rows
A = min(a for ls in legs.values() for a, _, _ in ls)
D = min(d for ls in legs.values() for _, d, _ in ls)
res = {}
for name, ls in legs.items():
    C = min(c for _, _, c in ls)
    tot = A + D + C
    nb = N // K
    res[name] = {
        "phase_ms": {"assemble": round(A / nb * 1e3, 1),
                     "device": round(D / nb * 1e3, 1),
                     "consume": round(C / nb * 1e3, 1)},
        "host_share": round((A + C) / tot, 3),
        "host_assembly_frac": round(A / tot, 3),
        "consume_frac": round(C / tot, 3),
        "windows_per_s": round(N / tot, 1),
    }

ident = rows_by["perwindow"] == rows_by["batched"]
pa, pb = sys_by["perwindow"].predictor, sys_by["batched"].predictor
for x, y in zip(jax.tree.leaves(pa.replay), jax.tree.leaves(pb.replay)):
    ident = ident and bool((np.asarray(x) == np.asarray(y)).all())
ident = ident and pa.stats == pb.stats \
    and bool((pa._replay_times == pb._replay_times).all())
cpw = res["perwindow"]["phase_ms"]["consume"]
cb = res["batched"]["phase_ms"]["consume"]
print(json.dumps({
    "bit_identical": bool(ident),
    "perwindow": res["perwindow"],
    "batched": res["batched"],
    "consume_speedup": round(cpw / max(cb, 1e-9), 2),
    "cell": {"K": K, "E": E, "S": S, "T": T, "M": M,
             "records_per_stream_window": PER},
}))
"""


def bench_predictor_batch(quick=False):
    import subprocess

    import jax

    from repro.core.reward import energy_reward_spec
    from repro.runtime.predictor import (ActionSpace, Predictor,
                                         linear_policy)

    # --- identity + dispatch-cost cell (in-process, exact) ----------------
    E, F, K = 8, 8, 32

    def mkp():
        return Predictor(
            linear_policy(F, 2),
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            E, F, replay_capacity=64)

    rng = np.random.RandomState(0)
    feats = rng.normal(0, 1, (K, E, F)).astype(np.float32)
    raw = rng.normal(5, 2, (K, E, F)).astype(np.float32)
    times = [60.0 * (j + 1) for j in range(K)]
    a, b = mkp(), mkp()
    seq = [a.on_tick(feats[j], times[j], raw=raw[j]) for j in range(K)]
    act, rew, per = b.on_windows(feats, times, raw=raw)
    ident = ((np.stack([s[0] for s in seq]) == act).all()
             and (np.stack([s[1] for s in seq]) == rew).all()
             and (np.stack([s[2] for s in seq]) == per).all()
             and all(bool((np.asarray(x) == np.asarray(y)).all())
                     for x, y in zip(jax.tree.leaves(a.replay),
                                     jax.tree.leaves(b.replay))))
    SUMMARY["predictor_batch_bit_identical"] = bool(ident)

    n = 4 if quick else 8
    t_pw = _time(lambda: [a.on_tick(feats[j], times[j], raw=raw[j])
                          for j in range(K)], n=n, best=True)
    t_b = _time(lambda: b.on_windows(feats, times, raw=raw), n=n, best=True)
    SUMMARY["predictor_consume_speedup"] = round(t_pw / t_b, 2)
    _row(f"predictor_batch_K{K}_E{E}", t_b / K,
         f"on_windows {1e6 / (t_b / K):.0f} windows/s (1 dispatch) | "
         f"Kx on_tick {t_pw / K:.0f} us/win | speedup {t_pw / t_b:.2f}x | "
         f"bit_identical {ident}")

    # --- before/after on the PR 3 overlap cell (subprocess) ---------------
    env = _subprocess_env("--xla_cpu_multi_thread_eigen=false")
    script = _PRED_BATCH_SCRIPT.replace("__QUICK__", str(bool(quick)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    cell = json.loads(out.stdout.strip().splitlines()[-1])
    SUMMARY["predictor_batch"] = cell
    pw, bt = cell["perwindow"], cell["batched"]
    _row("predictor_batch_overlap_cell_K32_E8_S8_T64",
         1e6 / bt["windows_per_s"],
         f"{bt['windows_per_s']:.0f} windows/s | consume "
         f"{pw['phase_ms']['consume']:.1f} -> {bt['phase_ms']['consume']:.1f}"
         f" ms/batch ({cell['consume_speedup']:.1f}x) | host share "
         f"{pw['host_share']:.0%} -> {bt['host_share']:.0%} of scan wall | "
         f"bit_identical {cell['bit_identical']}")


# --------------------------------------------------------------------------
# Table 2i — host ingest fast path: arena staging + sorted-merge bucketing
#            + one-pass multi-env assembly
# --------------------------------------------------------------------------

# Phase decomposition of the PR 3 overlap cell focused on the A term: twin
# scan systems drain identical published batches, one through the legacy
# chunk-list + global-lexsort accumulators (``ingest_fastpath=False``), one
# through the fleet-wide pass. D and C run identical code on both twins, so
# only the assemble phase is compared; legs are interleaved with identical
# publish seeds and bit-identity of every output row is asserted.
_INGEST_FASTPATH_SCRIPT = """
import json, time
import numpy as np
import jax
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.records import RecordBatch
from repro.runtime.system import PerceptaSystem, SourceSpec

E, S, K, M = 8, 8, 32, 64
T, TICK_S, PER = 64, 15.0, 160

def mk(fast):
    srcs = [SourceSpec(f"s{i}", "mqtt",
                       SimulatedDevice(f"st{i}", 60.0, base=3.0, seed=i))
            for i in range(S)]
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=TICK_S,
                         max_samples=M, harmonize_method="onehot",
                         gap_strategy="linear")
    pred = Predictor(linear_policy(S, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     E, cfg.n_features, replay_capacity=64)
    return PerceptaSystem([f"b{i}" for i in range(E)], srcs, cfg, pred,
                          speedup=1e9, manual_time=True, mode="scan",
                          scan_k=K, ingest_fastpath=fast)

def publish(s, n_windows, rng):
    # per-poll columns, time-sorted and honestly flagged -- the shape the
    # MQTT receiver hands over (it measures sortedness per poll)
    w = s.window_s
    n = n_windows * PER
    t0 = s.window_bounds(s.window_index)[0]
    for env in s.env_ids:
        for src in s.sources:
            ts = np.sort(rng.uniform(t0, t0 + n_windows * w, n))
            s.broker.publish(RecordBatch.from_columns(
                env, src.device.stream, ts, rng.normal(5, 2, n),
                sorted_ts=True))

QUICK = __QUICK__
N = 64 if QUICK else 96
REPS = 2 if QUICK else 3

def measure(s, rows):
    A = D = C = 0.0
    for b in range(N // K):
        bounds = [s.window_bounds(s.window_index + j) for j in range(K)]
        t0 = time.time(); raw, counts = s.assemble_windows(bounds)
        A += time.time() - t0
        t0 = time.time()
        feats, frames, td = s._dispatch_scan(raw, K)
        jax.block_until_ready(feats.features)
        D += time.time() - t0
        t0 = time.time()
        out = s._consume_scan(bounds, counts, feats, frames, td)
        C += time.time() - t0
        rows.extend({k: v for k, v in r.items() if k != "latency_s"}
                    for r in out)
    return A, D, C

sys_by = {"legacy": mk(False), "fast": mk(True)}
rows_by = {}
legs = {name: [] for name in sys_by}
for s in sys_by.values():
    s.run_windows(K, pump=False)                 # jit/cache warmup
for rep in range(REPS):                          # identical publish seeds
    for name, s in sys_by.items():
        publish(s, N, np.random.RandomState(rep))
        rows = []
        legs[name].append(measure(s, rows))
        rows_by[name] = rows

nb = N // K
D = min(d for ls in legs.values() for _, d, _ in ls)
C = min(c for ls in legs.values() for _, _, c in ls)
a_ms = {name: round(min(a for a, _, _ in ls) / nb * 1e3, 1)
        for name, ls in legs.items()}
ident = rows_by["fast"] == rows_by["legacy"]
ms = dict(sys_by["fast"].fleet.merge_stats)
for s in sys_by.values():
    s.stop()
n_records = E * S * N * PER                      # per leg, by construction
print(json.dumps({
    "bit_identical": bool(ident),
    "legacy_assemble_ms": a_ms["legacy"],
    "fast_assemble_ms": a_ms["fast"],
    "assemble_speedup": round(a_ms["legacy"] / max(a_ms["fast"], 1e-9), 2),
    # ingest throughput through the fast assemble phase alone
    "records_per_s": round(n_records / (a_ms["fast"] * 1e-3 * nb), 1),
    # every close on this cell should order its groups without a
    # timestamp sort
    "merge_stats_fast": ms,
    "sorted_fastpath_hit_rate": round(
        1 - ms["close_lexsort"] / max(sum(ms.values()), 1), 3),
    "scan_phase_ms": {"assemble": a_ms["fast"],
                      "device": round(D / nb * 1e3, 1),
                      "consume": round(C / nb * 1e3, 1)},
    "cell": {"K": K, "E": E, "S": S, "T": T, "M": M,
             "records_per_stream_window": PER},
}))
"""


def bench_ingest_fastpath(quick=False):
    import subprocess

    env = _subprocess_env("--xla_cpu_multi_thread_eigen=false")
    script = _INGEST_FASTPATH_SCRIPT.replace("__QUICK__", str(bool(quick)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    cell = json.loads(out.stdout.strip().splitlines()[-1])
    SUMMARY["ingest_fastpath"] = cell
    _row("ingest_fastpath_overlap_cell_K32_E8_S8_T64",
         cell["fast_assemble_ms"] * 1e3 / cell["cell"]["K"],
         f"assemble {cell['legacy_assemble_ms']:.1f} -> "
         f"{cell['fast_assemble_ms']:.1f} ms/batch "
         f"({cell['assemble_speedup']:.1f}x) | "
         f"{cell['records_per_s']:.0f} records/s | sorted fast-path hit "
         f"{cell['sorted_fastpath_hit_rate']:.0%} | "
         f"bit_identical {cell['bit_identical']}")


# --------------------------------------------------------------------------
# Table 2f — device-resident decision path: fused decide vs two dispatches
# --------------------------------------------------------------------------

def bench_fused_decide(quick=False):
    """Three cells for the fused decision engine:

    * identity (system level, K=32/E=8): ``scan_fused_decide`` results +
      replay export bit-identical to the two-dispatch reference;
    * acceptance (engine level, K=32/E=256 — the per-device regime): the
      fused single dispatch vs ``run_many`` + ``on_windows`` + the
      consume fetches, with phase decomposition and measured host-transfer
      bytes per batch (the fused path fetches only the small per-window
      outputs);
    * sharded (K=32/E=256 on the visible env mesh — 8 devices under
      ``--host-devices 8``): fused carry env-sharded, bit-identity vs the
      unsharded fused engine asserted.
    Legs of the acceptance cell are interleaved (ratio of totals) so
    shared-box drift cancels, same protocol as the overlap cells.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import PipelineConfig
    from repro.core import pipeline as pl
    from repro.core.frame import make_raw_window
    from repro.core.reward import energy_reward_spec
    from repro.runtime.predictor import (ActionSpace, Predictor,
                                         linear_policy)
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import PerceptaSystem, SourceSpec

    # --- identity cell (system level) -------------------------------------
    def mk(mode):
        srcs = [SourceSpec(f"s{i}", "mqtt",
                           SimulatedDevice(f"st{i}", 60.0, base=3.0, seed=i))
                for i in range(8)]
        cfg = PipelineConfig(n_envs=8, n_streams=8, n_ticks=16, tick_s=60.0,
                             max_samples=64)
        pred = Predictor(
            linear_policy(8, 2),
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            8, cfg.n_features, replay_capacity=64)
        return PerceptaSystem([f"b{i}" for i in range(8)], srcs, cfg, pred,
                              speedup=1e9, manual_time=True, mode=mode,
                              scan_k=32)

    n = 32 if quick else 64
    strip = lambda rs: [{k: v for k, v in r.items() if k != "latency_s"}
                        for r in rs]
    ref, fus = mk("scan"), mk("scan_fused_decide")
    ident = strip(ref.run_windows(n)) == strip(fus.run_windows(n))
    ea, eb = ref.export_replay("bench"), fus.export_replay("bench")
    for key in ("obs", "actions", "rewards", "next_obs", "tick_idx",
                "times"):
        ident = ident and bool(
            (np.asarray(ea[key]) == np.asarray(eb[key])).all())
    ref.stop(), fus.stop()
    SUMMARY["fused_decide_bit_identical"] = bool(ident)
    _row("fused_decide_identity_K32_E8_S8", 0.0,
         f"bit_identical {ident} over {n} windows "
         f"(results + rolled replay export w/ reconstructed times)")

    # --- acceptance cell: K=32, E=256, one dispatch vs two ----------------
    # the high-cadence edge regime the fused engine targets: short windows
    # (8 ticks), the Predictor's DEFAULT 4096-slot replay ring. The
    # two-dispatch path re-copies the full (E, 4096, F) ring storage every
    # on_windows dispatch (its jit cannot donate — the Predictor owns the
    # buffer across calls) and ships features + frames to the host; the
    # fused engine updates the donated ring in place and ships only the
    # small DecideBatch leaves.
    K, E, S, T, M, CAP = 32, 256, 8, 8, 16, 4096
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                         max_samples=M)
    F = cfg.n_features
    rng = np.random.RandomState(0)
    raws = make_raw_window(
        rng.normal(5, 2, (K, E, S, M)).astype(np.float32),
        rng.uniform(0, T * 60, (K, E, S, M)).astype(np.float32),
        rng.rand(K, E, S, M) > 0.3)
    starts = jnp.zeros((K, E), jnp.float32)
    times = [T * 60.0 * (j + 1) for j in range(K)]
    denom = float(E * S * T)

    def mkp():
        return Predictor(
            linear_policy(F, 2),
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            E, F, replay_capacity=CAP)

    # two-dispatch reference: exactly the scan-mode Manager's device +
    # consume work (run_many, on_windows, the batch-wide fetches, the
    # per-window metric loop over the (E, S, T) frames)
    p_ref = mkp()
    pipe = pl.PerceptaPipeline(cfg, mode="scan", donate=True)
    ref_state = [pl.init_state(cfg)]
    ref_bytes = [0]

    def run_ref():
        t0 = time.time()
        ref_state[0], feats, frames = pipe.run_many(ref_state[0], raws,
                                                    starts)
        jax.block_until_ready(feats.features)
        t1 = time.time()
        acts, rews, _per = p_ref.on_windows(feats.features, times,
                                            raw=feats.raw)
        feat_np = np.asarray(feats.features)
        obs_np = np.asarray(frames.observed)
        fill_np = np.asarray(frames.filled)
        anom_np = np.asarray(frames.anomalous)
        metrics = [(float(np.mean(rews[j])), float(obs_np[j].mean()),
                    float(fill_np[j].mean()), int(anom_np[j].sum()))
                   for j in range(K)]
        ref_bytes[0] = (feat_np.nbytes + obs_np.nbytes + fill_np.nbytes
                        + anom_np.nbytes + acts.nbytes + rews.nbytes
                        + _per.nbytes)
        return t1 - t0, time.time() - t1, acts, rews, metrics

    # fused: one dispatch; the host touches only the small output leaves
    p_fus = mkp()
    from repro import compat
    engine = compat.jit_donated(
        functools.partial(pl.run_many_decide, cfg, p_fus.make_decide_fn()),
        donate_argnums=(0, 1))
    fus_state = [pl.init_state(cfg), p_fus.decide_state()]
    fus_bytes = [0]

    def run_fused():
        t0 = time.time()
        fus_state[0], fus_state[1], outs = engine(fus_state[0], fus_state[1],
                                                  raws, starts)
        jax.block_until_ready(outs.rewards)
        t1 = time.time()
        acts = np.asarray(outs.actions)
        rews = np.asarray(outs.rewards)
        viol = np.asarray(outs.violated)
        obs_c = np.asarray(outs.observed)
        fill_c = np.asarray(outs.filled)
        anom_c = np.asarray(outs.anomalous)
        p_fus.absorb_fused(times, viol)
        metrics = [(float(np.mean(rews[j])),
                    float(int(obs_c[j].sum()) / denom),
                    float(int(fill_c[j].sum()) / denom),
                    int(anom_c[j].sum()))
                   for j in range(K)]
        fus_bytes[0] = (acts.nbytes + rews.nbytes + viol.nbytes
                        + obs_c.nbytes + fill_c.nbytes + anom_c.nbytes)
        return t1 - t0, time.time() - t1, acts, rews, metrics

    # warmup + engine-level bit-identity (fresh twin states)
    _, _, a_ref, r_ref, m_ref = run_ref()
    _, _, a_fus, r_fus, m_fus = run_fused()
    cell_ident = (bool((a_ref == a_fus).all())
                  and bool((r_ref == r_fus).all()) and m_ref == m_fus)

    # interleaved pairs; the headline speedup is the MEDIAN of per-pair
    # ratios (same protocol as the overlap cells: shared-box throughput
    # drifts on minute timescales, and a couple of congested pairs poison
    # a ratio of totals but not a median)
    pairs = 4 if quick else 8
    legs = {"ref": [0.0, 0.0], "fused": [0.0, 0.0]}
    ratios = []
    nb = 0
    for _pair in range(pairs):
        d, c, *_ = run_ref()
        legs["ref"][0] += d
        legs["ref"][1] += c
        d2, c2, *_ = run_fused()
        legs["fused"][0] += d2
        legs["fused"][1] += c2
        ratios.append((d + c) / (d2 + c2))
        nb += 1
    tot_ref = sum(legs["ref"])
    tot_fus = sum(legs["fused"])
    wps_ref = K * nb / tot_ref
    wps_fus = K * nb / tot_fus
    speedup = float(np.median(ratios))
    xfer_ratio = ref_bytes[0] / max(fus_bytes[0], 1)
    SUMMARY["windows_per_s"]["fused_decide_two_dispatch_E256"] = \
        round(wps_ref, 1)
    SUMMARY["windows_per_s"]["fused_decide_E256"] = round(wps_fus, 1)
    SUMMARY["fused_decide"] = {
        "cell": {"K": K, "E": E, "S": S, "T": T, "M": M,
                 "replay_capacity": CAP},
        "bit_identical": cell_ident,
        "speedup": round(speedup, 2),
        "speedup_ratio_of_totals": round(tot_ref / tot_fus, 2),
        "pair_ratios": [round(r, 2) for r in ratios],
        "phase_ms_two_dispatch": {
            "device": round(legs["ref"][0] / nb * 1e3, 1),
            "consume": round(legs["ref"][1] / nb * 1e3, 1)},
        "phase_ms_fused": {
            "device": round(legs["fused"][0] / nb * 1e3, 1),
            "consume": round(legs["fused"][1] / nb * 1e3, 1)},
        "host_transfer_bytes_two_dispatch": int(ref_bytes[0]),
        "host_transfer_bytes_fused": int(fus_bytes[0]),
        "host_transfer_reduction": round(xfer_ratio, 1),
    }
    _row(f"fused_decide_K{K}_E{E}", 1e6 / wps_fus,
         f"{wps_fus:.0f} windows/s (1 dispatch end-to-end) vs "
         f"{wps_ref:.0f} two-dispatch | speedup {speedup:.2f}x "
         f"(median of {nb} interleaved pair ratios; ratio of totals "
         f"{tot_ref / tot_fus:.2f}x) | host transfer "
         f"{ref_bytes[0] / 2**20:.2f} -> "
         f"{fus_bytes[0] / 2**20:.3f} MiB/batch ({xfer_ratio:.0f}x less) | "
         f"bit_identical {cell_ident}")

    # --- sharded cell: E=256 on the visible env mesh ----------------------
    # measured with the SAME estimator as the unsharded fused cell
    # (interleaved legs, ratio of totals) so the recorded sharded-vs-fused
    # ratio doesn't mix a best-of min against drift-inclusive totals
    p_sh = mkp()
    sh_engine, mesh = pl.make_run_many_decide_sharded(
        cfg, p_sh.make_decide_fn(), p_sh.decide_state())
    sh_engine = compat.jit_donated(sh_engine, donate_argnums=(0, 1))
    sh_state = [pl.init_state(cfg), p_sh.decide_state()]

    def run_sharded():
        t0 = time.time()
        sh_state[0], sh_state[1], outs = sh_engine(sh_state[0], sh_state[1],
                                                   raws, starts)
        jax.block_until_ready(outs.rewards)
        return time.time() - t0, outs

    _, outs_sh = run_sharded()       # warmup + identity vs unsharded fused
    sh_ident = bool((np.asarray(outs_sh.actions) == a_fus).all())
    run_sharded()                    # second warmup: the first donated
    #                                  re-dispatch can trigger a slow lazy
    #                                  XLA path; exclude it like a compile
    pairs_sh = 4 if quick else 8
    tot_f2 = tot_sh = 0.0
    sh_ratios = []
    for _pair in range(pairs_sh):
        d, c, *_ = run_fused()
        tot_f2 += d + c
        dt, _ = run_sharded()
        tot_sh += dt
        sh_ratios.append((d + c) / dt)
    wps_sh = K * pairs_sh / tot_sh
    mesh_speedup = float(np.median(sh_ratios))
    mesh_n = int(np.prod(list(mesh.shape.values())))
    SUMMARY["windows_per_s"]["fused_decide_sharded_E256"] = round(wps_sh, 1)
    SUMMARY["fused_decide_sharded_bit_identical"] = sh_ident
    SUMMARY["fused_decide_mesh_speedup"] = round(mesh_speedup, 2)
    _row(f"fused_decide_sharded_K{K}_E{E}", 1e6 / wps_sh,
         f"{wps_sh:.0f} windows/s | {mesh_n}-device env mesh "
         f"({E // mesh_n} envs/device) | {mesh_speedup:.2f}x vs unsharded "
         f"fused (median of {pairs_sh} interleaved pair ratios) | "
         f"bit_identical-to-fused {sh_ident}")


def bench_contract_check(quick=False):
    """Construction-overhead guard for the PR 6 invariant gate: the jaxpr
    contract check that ``PerceptaSystem`` runs for fused/``_sharded``
    modes must add <1% to standing a fused system up (construction through
    the first K-batch dispatch — bare ``__init__`` is single-digit ms, so
    the meaningful denominator is the time to a RUNNING system, which the
    first dispatch's compile dominates).

    Two estimators, both min-of-reps (shared-box robust):

    * direct — ``analysis.check_system`` on a live system's freshly built
      ``DecideFns`` (fresh closures, so no trace-cache hits: exactly the
      cold construction-time cost). This is the asserted number.
    * paired — interleaved ``contract_check=True`` vs ``False``
      construction-to-first-dispatch legs, reported for context (its
      delta is compile-time noise plus the check).
    """
    import time as _time

    from repro import analysis
    from repro.core import PipelineConfig
    from repro.core.reward import energy_reward_spec
    from repro.runtime.predictor import (ActionSpace, Predictor,
                                         linear_policy)
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import PerceptaSystem, SourceSpec

    # the fused acceptance regime (same shapes as the fused_decide cell)
    K, E, S, T, M, CAP = 32, 256, 8, 8, 16, 4096

    def stand_up(check):
        srcs = [SourceSpec(f"s{i}", "mqtt",
                           SimulatedDevice(f"st{i}", 60.0, base=3.0, seed=i))
                for i in range(S)]
        cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                             max_samples=M)
        pred = Predictor(
            linear_policy(S, 2),
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            E, cfg.n_features, replay_capacity=CAP)
        t0 = _time.perf_counter()
        s = PerceptaSystem([f"b{i}" for i in range(E)], srcs, cfg, pred,
                           speedup=1e9, manual_time=True,
                           mode="scan_fused_decide", scan_k=K,
                           contract_check=check)
        s.run_windows(K)
        return s, _time.perf_counter() - t0

    stand_up(True)[0].stop()      # process warmup (imports, jit plumbing)
    reps = 2 if quick else 3
    base, checked, direct = [], [], []
    for _ in range(reps):
        s, dt = stand_up(False)
        base.append(dt)
        # cold check cost on THIS system: fresh DecideFns closures miss
        # every trace cache, reproducing the construction-time call
        d = s.predictor.make_decide_fn()
        t0 = _time.perf_counter()
        analysis.check_system(s.predictor, decide=d, dstate=s._dstate,
                              sharded=False)
        direct.append(_time.perf_counter() - t0)
        s.stop()
        s, dt = stand_up(True)
        checked.append(dt)
        s.stop()

    check_ms = min(direct) * 1e3
    base_s = min(base)
    pct = 100.0 * min(direct) / base_s
    paired_pct = 100.0 * (min(checked) - base_s) / base_s
    SUMMARY["contract_check"] = {
        "check_ms": round(check_ms, 1),
        "standup_s": round(base_s, 3),
        "overhead_pct": round(pct, 3),
        "paired_pct": round(paired_pct, 3),
    }
    _row(f"contract_check_K{K}_E{E}", check_ms * 1e3,
         f"{check_ms:.1f} ms cold check | {pct:.2f}% of the {base_s:.2f}s "
         f"construction-to-first-dispatch standup (paired delta "
         f"{paired_pct:+.2f}%) | budget <1%")
    assert pct < 1.0, (
        f"construction-time contract check costs {pct:.2f}% of fused-mode "
        f"system standup ({check_ms:.1f} ms / {base_s:.2f} s) — over the "
        "1% budget")


def bench_certify(quick=False):
    """Certification-cost cells for the PR 8 policy registry gate
    (``runtime.policies`` -> ``analysis.certify``):

    * cold — ``certify_policy`` over every registered policy with a
      cleared cache: trace + full rule walk (recurrent-carry fixed point,
      pallas BlockSpec recursion) + the two-env-count param-replication
      probe, per policy. This is the one-time cost a registry policy pays
      the FIRST time it is stood up in a process.
    * cached — the certificate-cache hit every repeated standup of the
      same policy pays instead (the construction path of
      ``PerceptaSystem(..., policy=...)``), measured against the fused
      acceptance-regime standup (K=32, E=256, construction through the
      first K-batch dispatch): must add <1% (asserted — mirroring the
      PR 6 contract-check budget).
    """
    import time as _time

    from repro.analysis import certify
    from repro.core import PipelineConfig
    from repro.core.reward import energy_reward_spec
    from repro.runtime.policies import POLICIES
    from repro.runtime.predictor import ActionSpace, Predictor
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import PerceptaSystem, SourceSpec

    # cold path: full-catalog certification of the whole registry
    certify.clear_cache()
    cold = {}
    for key, builder in POLICIES.items():
        t0 = _time.perf_counter()
        certify.certify_policy(builder, name=key)
        cold[key] = (_time.perf_counter() - t0) * 1e3
    cold_ms = sum(cold.values())

    # cached path: populate once, then time the hits (the repeated-standup
    # cost — certify_policy returns the stored certificate by key)
    for key, builder in POLICIES.items():
        certify.certify_policy(builder, name=key, cache_key=("bench", key))
    t0 = _time.perf_counter()
    for key, builder in POLICIES.items():
        certify.certify_policy(builder, name=key, cache_key=("bench", key))
    cached_ms = (_time.perf_counter() - t0) * 1e3

    # denominator: standing up a REAL registry policy ("rglru", stateful
    # carry in the fused scan) at the fused acceptance regime; the
    # predictor resolves the name through build_policy, so construction
    # itself exercises the cached certification path after the warmup
    K, E, S, T, M, CAP = 32, 256, 8, 8, 16, 4096

    def stand_up():
        srcs = [SourceSpec(f"s{i}", "mqtt",
                           SimulatedDevice(f"st{i}", 60.0, base=3.0, seed=i))
                for i in range(S)]
        cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                             max_samples=M)
        pred = Predictor(
            "rglru",
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            E, cfg.n_features, replay_capacity=CAP)
        t0 = _time.perf_counter()
        s = PerceptaSystem([f"b{i}" for i in range(E)], srcs, cfg, pred,
                           speedup=1e9, manual_time=True,
                           mode="scan_fused_decide", scan_k=K)
        s.run_windows(K)
        return s, _time.perf_counter() - t0

    stand_up()[0].stop()          # warmup (jit plumbing + the F-probe cache)
    reps = 1 if quick else 2
    standups = []
    for _ in range(reps):
        s, dt = stand_up()
        standups.append(dt)
        s.stop()
    base_s = min(standups)
    pct = 100.0 * (cached_ms / 1e3) / base_s
    cold_pct = 100.0 * (cold_ms / 1e3) / base_s
    SUMMARY["certify"] = {
        "cold_ms": {k: round(v, 1) for k, v in cold.items()},
        "cold_total_ms": round(cold_ms, 1),
        "cached_ms": round(cached_ms, 3),
        "standup_s": round(base_s, 3),
        "cached_overhead_pct": round(pct, 4),
        "cold_overhead_pct": round(cold_pct, 2),
    }
    _row(f"certify_cold_{len(POLICIES)}policies", cold_ms * 1e3,
         " | ".join(f"{k} {v:.0f} ms" for k, v in cold.items())
         + " | full catalog, cleared cache")
    _row(f"certify_cached_K{K}_E{E}", cached_ms * 1e3,
         f"{cached_ms:.2f} ms for all {len(POLICIES)} cache hits | "
         f"{pct:.3f}% of the {base_s:.2f}s rglru fused standup "
         f"(cold would be {cold_pct:.1f}%) | budget <1%")
    assert pct < 1.0, (
        f"cached policy certification costs {pct:.3f}% of fused-mode "
        f"system standup ({cached_ms:.2f} ms / {base_s:.2f} s) — over the "
        "1% budget")


def bench_online_train(quick=False):
    """Two cells for the device-resident online retraining path (PR 7):

    * sample+update (full E=256 x C=4096 ring, F=8, A=4): the jitted
      ``sample_device`` + AdamW step — ONE dispatch touching only
      ``batch`` sampled rows — vs the host round-trip it replaces:
      ``export_for_training`` (full-ring device->host copy, chronological
      roll, env-id anonymization) + numpy minibatch gather + the same
      closed-form TD gradients and AdamW in numpy. Acceptance: the
      device step >= 3x the export path.
    * overlapped serving (the K=32/E=256 fused cell): windows/s of the
      fused decide engine driving the trainer's batch-boundary protocol
      (``apply_pending`` before the dispatch, ``dispatch`` after) ON vs
      OFF — the train step rides the dispatch bubble, so the serving
      cost bound is <= 10%.
    Both cells interleave their legs and report the MEDIAN of per-pair
    ratios (the shared-box drift protocol of the overlap cells).
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.core import PipelineConfig
    from repro.core import pipeline as pl
    from repro.core import replay as rp
    from repro.core.frame import make_raw_window
    from repro.core.reward import energy_reward_spec
    from repro.runtime.predictor import (ActionSpace, Predictor,
                                         linear_policy)
    from repro.runtime.trainer import OnlineTrainer, default_train_cfg

    # --- cell i: device sample+update vs host export + numpy update -------
    E, CAP, F, A, B = 256, 4096, 8, 4, 256
    cfg_t = default_train_cfg()
    rngn = np.random.RandomState(0)
    pred = Predictor(linear_policy(F, A),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.full(A, -1.0), np.full(A, 1.0)),
                     E, F, replay_capacity=CAP)
    trainer = OnlineTrainer(pred, batch_size=B, train_cfg=cfg_t)
    # fill the ring in one scatter (CAP ticks of E envs)
    buf = rp.add_batch(
        rp.init(E, CAP, F, A),
        jnp.asarray(rngn.normal(0, 1, (CAP, E, F)), jnp.float32),
        jnp.asarray(rngn.uniform(-1, 1, (CAP, E, A)), jnp.float32),
        jnp.asarray(rngn.normal(0, 2, (CAP, E)), jnp.float32),
        jnp.asarray(rngn.normal(0, 1, (CAP, E, F)), jnp.float32),
        jnp.arange(CAP, dtype=jnp.int32))
    jax.block_until_ready(buf.obs)

    steps = 2 if quick else 4
    dev = [pred.policy_params, trainer.train_state]
    key = [jax.random.PRNGKey(0)]

    def run_device():
        t0 = time.time()
        for _ in range(steps):
            key[0], sub = jax.random.split(key[0])
            p, st, loss, gn, hd = trainer.step_fn(dev[0], dev[1], buf, sub)
            dev[0], dev[1] = p, st
        jax.block_until_ready(dev[0]["w"])
        return time.time() - t0

    # numpy mirror of the SAME update: closed-form grads of td_loss
    # (critic regression + 0.1 * policy-through-critic) + global-norm
    # clip + AdamW with the same schedule (train/optimizer.py)
    env_ids = [f"env-{i}" for i in range(E)]
    hrng = np.random.RandomState(1)
    h = {"w": np.asarray(pred.policy_params["w"], np.float32).copy(),
         "qw": np.zeros(F + A, np.float32), "qb": np.float32(0.0)}
    hm = {k: np.zeros_like(v) for k, v in h.items()}
    hv = {k: np.zeros_like(v) for k, v in h.items()}
    hstep = [0]

    def run_host():
        t0 = time.time()
        for _ in range(steps):
            exp = rp.export_for_training(buf, env_ids, "bench")
            obs = np.asarray(exp["obs"]).reshape(-1, F)
            acts = np.asarray(exp["actions"]).reshape(-1, A)
            rews = np.asarray(exp["rewards"]).reshape(-1)
            idx = hrng.randint(0, obs.shape[0], B)
            o, a, r = obs[idx], acts[idx], rews[idx]
            X = np.concatenate([o, a], 1)
            e = X @ h["qw"] + h["qb"] - r
            a_pi = np.tanh(o @ h["w"])
            Xp = np.concatenate([o, a_pi], 1)
            g = {"qw": 2.0 / B * X.T @ e - 0.1 / B * Xp.sum(0),
                 "qb": np.float32(2.0 / B * e.sum() - 0.1),
                 "w": -0.1 / B * o.T @ ((1 - a_pi ** 2)
                                        * h["qw"][F:][None, :])}
            gn = np.sqrt(sum(float((x ** 2).sum()) for x in g.values()))
            scale = min(1.0, cfg_t.grad_clip / max(gn, 1e-12))
            hstep[0] += 1
            s = hstep[0]
            t = np.clip((s - cfg_t.warmup_steps)
                        / max(cfg_t.total_steps - cfg_t.warmup_steps, 1),
                        0.0, 1.0)
            lr = cfg_t.learning_rate * (0.1 + 0.9 * 0.5
                                        * (1 + np.cos(np.pi * t)))
            c1 = 1 - cfg_t.beta1 ** s
            c2 = 1 - cfg_t.beta2 ** s
            for k2 in h:
                gk = g[k2] * scale
                hm[k2] = cfg_t.beta1 * hm[k2] + (1 - cfg_t.beta1) * gk
                hv[k2] = cfg_t.beta2 * hv[k2] + (1 - cfg_t.beta2) * gk ** 2
                h[k2] = h[k2] - lr * ((hm[k2] / c1)
                                      / (np.sqrt(hv[k2] / c2) + cfg_t.eps))
        return time.time() - t0

    run_device(), run_host()          # warmup (compile / first export)
    pairs = 3 if quick else 5
    t_dev = t_host = 0.0
    ratios = []
    for _pair in range(pairs):
        th = run_host()
        td = run_device()
        t_host += th
        t_dev += td
        ratios.append(th / td)
    speedup = float(np.median(ratios))
    dev_ms = t_dev / (pairs * steps) * 1e3
    host_ms = t_host / (pairs * steps) * 1e3
    assert np.isfinite(h["w"]).all() and np.isfinite(
        np.asarray(dev[0]["w"])).all()
    SUMMARY["online_train"] = {
        "cell": {"E": E, "capacity": CAP, "F": F, "A": A, "batch": B},
        "device_step_ms": round(dev_ms, 2),
        "host_export_step_ms": round(host_ms, 2),
        "speedup": round(speedup, 2),
        "pair_ratios": [round(r, 2) for r in ratios],
    }
    _row(f"online_train_sample_update_E{E}_C{CAP}", dev_ms * 1e3,
         f"{dev_ms:.2f} ms device sample+update vs {host_ms:.1f} ms "
         f"export+numpy | {speedup:.1f}x (median of {pairs} interleaved "
         f"pair ratios) | acceptance >=3x")

    # --- cell ii: serving windows/s with overlapped training on vs off ---
    K, E2, S, T, M = 32, 256, 8, 8, 16
    cfg = PipelineConfig(n_envs=E2, n_streams=S, n_ticks=T, tick_s=60.0,
                         max_samples=M)
    F2 = cfg.n_features
    raws = make_raw_window(
        rngn.normal(5, 2, (K, E2, S, M)).astype(np.float32),
        rngn.uniform(0, T * 60, (K, E2, S, M)).astype(np.float32),
        rngn.rand(K, E2, S, M) > 0.3)
    starts = jnp.zeros((K, E2), jnp.float32)

    def mk_leg(train):
        p = Predictor(
            linear_policy(F2, 2),
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            E2, F2, replay_capacity=4096)
        engine = compat.jit_donated(
            functools.partial(pl.run_many_decide, cfg, p.make_decide_fn()),
            donate_argnums=(0, 1))
        tr = OnlineTrainer(p, batch_size=B) if train else None
        state = [pl.init_state(cfg), p.decide_state()]

        def run():
            # the system's batch-boundary protocol (runtime/trainer.py
            # timeline): adopt the previous train result, serve, enqueue
            # the next train step behind the decide dispatch
            t0 = time.time()
            if tr is not None:
                state[1] = tr.apply_pending(state[1])
            state[0], state[1], outs = engine(state[0], state[1], raws,
                                              starts)
            if tr is not None:
                tr.dispatch(state[1])
            jax.block_until_ready(outs.rewards)
            # host consume of the small output leaves (fused-cell shape)
            rews = np.asarray(outs.rewards)
            _ = (np.asarray(outs.actions), np.asarray(outs.violated),
                 [float(np.mean(rews[j])) for j in range(K)])
            return time.time() - t0

        return run, (lambda: tr.train_stats() if tr else None)

    run_off, _ = mk_leg(train=False)
    run_on, stats_on = mk_leg(train=True)
    run_off(), run_on(), run_off(), run_on()     # warmup + donated redispatch
    pairs2 = 4 if quick else 8
    tot_off = tot_on = 0.0
    oh_ratios = []
    for _pair in range(pairs2):
        a_t = run_off()
        b_t = run_on()
        tot_off += a_t
        tot_on += b_t
        oh_ratios.append(b_t / a_t)
    wps_off = K * pairs2 / tot_off
    wps_on = K * pairs2 / tot_on
    overhead = float(np.median(oh_ratios))
    st = stats_on()
    SUMMARY["windows_per_s"]["fused_decide_train_off_E256"] = \
        round(wps_off, 1)
    SUMMARY["windows_per_s"]["fused_decide_train_on_E256"] = round(wps_on, 1)
    SUMMARY["online_train"]["overlap"] = {
        "overhead_ratio": round(overhead, 3),
        "pair_ratios": [round(r, 2) for r in oh_ratios],
        "train_steps_applied": st["applied"],
        "policy_version": st["version"],
    }
    _row(f"online_train_overlap_K{K}_E{E2}", 1e6 / wps_on,
         f"{wps_on:.0f} windows/s training-on vs {wps_off:.0f} off | "
         f"overhead {overhead:.3f}x (median of {pairs2} interleaved pair "
         f"ratios) | {st['applied']} updates applied, policy_version "
         f"{st['version']} | acceptance <=1.10x")


# --------------------------------------------------------------------------
# Table 2h — elastic slot pool: masked overhead at 75% occupancy + regrow
# --------------------------------------------------------------------------

def bench_elastic(quick=False):
    """Three cells for the elastic env-slot pool (PR 9):

    * identity: an elastic system holding 6 live envs in an 8-slot pool is
      bit-identical (per-window results + replay export) to a dense E=6
      fixed system over the same envs/streams;
    * overhead: interleaved batch pairs, elastic-under-churn vs the dense
      baseline — each pair the elastic system detaches one env and
      re-attaches it into the recycled slot (membership churn at a batch
      boundary, no retrace), and the MEDIAN per-pair wall ratio must stay
      <=1.10x (the 2 masked dead rows + mask select cost <10%);
    * regrow: one timed :meth:`resize` (8 -> 16 slots — pad, re-place,
      the single allowed retrace), then a post-regrow batch must produce
      finite stats on the surviving rows.
    """
    from repro.core import PipelineConfig
    from repro.core.reward import energy_reward_spec
    from repro.runtime.predictor import (ActionSpace, Predictor,
                                         linear_policy)
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import PerceptaSystem, SourceSpec

    SLOTS, ACTIVE, K = 8, 6, 8

    def mk(env_ids, slots=None, elastic=False):
        # off-tick intervals (9.7 / 31.3 s) so no reading lands exactly on
        # a window boundary (the float-boundary hazard the tests avoid too)
        srcs = [SourceSpec("grid_kw", "mqtt",
                           SimulatedDevice("grid", 9.7, base=3.0, seed=1)),
                SourceSpec("price_eur", "http",
                           SimulatedDevice("price", 31.3, base=0.2, seed=2))]
        n = slots if slots is not None else len(env_ids)
        cfg = PipelineConfig(n_envs=n, n_streams=2, n_ticks=8, tick_s=60.0,
                             max_samples=32)
        pred = Predictor(
            linear_policy(cfg.n_features, 2),
            energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
            ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
            n, cfg.n_features, replay_capacity=64)
        return PerceptaSystem(list(env_ids), srcs, cfg, pred,
                              speedup=5000.0, manual_time=True,
                              mode="scan_fused_decide", scan_k=K,
                              env_slots=slots, elastic=elastic)

    ids = [f"e{i}" for i in range(ACTIVE)]
    dense = mk(ids)
    el = mk(ids, slots=SLOTS, elastic=True)

    # --- identity: 6 live rows of 8 vs a dense E=6 system -----------------
    nwin = 2 * K if quick else 4 * K
    strip = lambda rs: [{k: v for k, v in r.items() if k != "latency_s"}
                        for r in rs]
    ident = strip(dense.run_windows(nwin)) == strip(el.run_windows(nwin))
    ea, eb = dense.export_replay("bench"), el.export_replay("bench")
    for key in ("obs", "actions", "rewards", "next_obs", "tick_idx"):
        ident = ident and bool(
            (np.asarray(ea[key])[:ACTIVE]
             == np.asarray(eb[key])[:ACTIVE]).all())
    SUMMARY["elastic_bit_identical"] = bool(ident)
    _row(f"elastic_identity_E{ACTIVE}_of_{SLOTS}", 0.0,
         f"bit_identical {ident} over {nwin} windows "
         f"(results + replay export, dense E={ACTIVE} reference)")

    # --- overhead under churn: interleaved pairs, median ratio ------------
    pairs = 3 if quick else 6
    tot_d = tot_e = 0.0
    ratios = []
    for p in range(pairs):
        t0 = time.time()
        dense.run_windows(K)
        d_t = time.time() - t0
        t0 = time.time()
        el.run_windows(K)
        e_t = time.time() - t0
        tot_d += d_t
        tot_e += e_t
        ratios.append(e_t / d_t)
        # churn at the batch boundary: detach one env, re-attach it into
        # the recycled slot (occupancy stays at ACTIVE/SLOTS, no retrace)
        victim = ids[p % ACTIVE]
        el.detach_env(victim)
        el.attach_env(victim)
    overhead = float(np.median(ratios))
    wps_d = K * pairs / tot_d
    wps_e = K * pairs / tot_e
    assert overhead <= 1.10, \
        f"masked slot-pool overhead {overhead:.3f}x > 1.10x acceptance"
    SUMMARY["windows_per_s"][f"elastic_E{ACTIVE}_of_{SLOTS}"] = \
        round(wps_e, 1)
    SUMMARY["windows_per_s"][f"elastic_dense_ref_E{ACTIVE}"] = \
        round(wps_d, 1)

    # --- regrow: one timed resize (8 -> 16), finite stats after -----------
    t0 = time.time()
    new_slots = el.resize()
    regrow_s = time.time() - t0
    post = el.run_windows(K)
    finite = all(np.isfinite(r["mean_reward"]) for r in post)
    dense.stop(), el.stop()
    SUMMARY["elastic"] = {
        "cell": {"slots": SLOTS, "active": ACTIVE, "K": K,
                 "occupancy": round(ACTIVE / SLOTS, 2)},
        "overhead_ratio": round(overhead, 3),
        "pair_ratios": [round(r, 2) for r in ratios],
        "churn_ops_per_pair": 2,
        "regrow_ms": round(regrow_s * 1e3, 1),
        "regrow_slots": [SLOTS, new_slots],
        "finite_after_regrow": bool(finite),
    }
    _row(f"elastic_overhead_E{ACTIVE}_of_{SLOTS}", 1e6 / wps_e,
         f"{wps_e:.0f} windows/s masked pool vs {wps_d:.0f} dense | "
         f"overhead {overhead:.3f}x (median of {pairs} interleaved pair "
         f"ratios, 1 detach+reattach churn per pair) | acceptance <=1.10x")
    _row(f"elastic_regrow_{SLOTS}_to_{new_slots}", regrow_s * 1e6,
         f"pool regrow {SLOTS} -> {new_slots} slots in "
         f"{regrow_s * 1e3:.0f} ms (pad + re-place + 1 retrace) | "
         f"finite_after_regrow {finite}")


def bench_autotune(quick=False):
    import jax

    from repro.core import PipelineConfig
    from repro.core.autotune import tune_scan_params

    cfg = PipelineConfig(n_envs=8, n_streams=8, n_ticks=16, tick_s=60.0,
                         max_samples=64)
    ndev = len(jax.devices())
    # short grid: windows-per-dispatch x env-mesh split (1 = plain scan,
    # ndev = the full forced mesh when bench-smoke runs --host-devices 8)
    counts = [1] if ndev == 1 else [1, min(8, ndev)]
    res = tune_scan_params(cfg, k_grid=(8, 32) if quick else (8, 16, 32),
                           device_counts=counts, reps=2 if quick else 3)
    optimum = max(w for _, _, w in res.grid)
    # acceptance is a fresh INDEPENDENT re-measurement of the chosen cell
    # (selection is the grid argmax by construction, so comparing it to its
    # own grid would be tautological): the chosen config re-measured on new
    # timings must still be within 10% of the calibration-grid optimum
    recheck = tune_scan_params(cfg, k_grid=(res.scan_k,),
                               device_counts=[res.mesh_devices],
                               reps=2 if quick else 3)
    within = recheck.best_windows_per_s >= 0.9 * optimum
    SUMMARY["autotune"] = res.as_dict() | {
        "remeasured_windows_per_s": round(recheck.best_windows_per_s, 1),
        "within_10pct_of_optimum": within}
    _row("autotune_scan_params", 1e6 / res.best_windows_per_s,
         f"chose scan_k={res.scan_k} mesh_devices={res.mesh_devices} "
         f"({res.best_windows_per_s:.0f} windows/s) over "
         f"{len(res.grid)}-cell grid | re-measured "
         f"{recheck.best_windows_per_s:.0f} windows/s, within 10% of grid "
         f"optimum: {within}")


# --------------------------------------------------------------------------
# Table 1b — columnar (RecordBatch) vs per-record host ingest + assembly
# --------------------------------------------------------------------------

class _LegacyAccumulator:
    """The seed's per-record ingest/close loop, kept verbatim as the
    benchmark baseline the columnar Accumulator is measured against."""

    def __init__(self, env_id, streams, max_samples):
        from collections import defaultdict
        self.env_id = env_id
        self.streams = list(streams)
        self.stream_index = {s: i for i, s in enumerate(self.streams)}
        self.max_samples = max_samples
        self._pending = defaultdict(list)
        self.stats = {"records": 0, "unknown_stream": 0, "overflow": 0}

    def ingest(self, records):
        for r in records:
            idx = self.stream_index.get(r.stream)
            if idx is None:
                self.stats["unknown_stream"] += 1
                continue
            self.stats["records"] += 1
            self._pending[idx].append(r)

    def close_window(self, t_start, t_end):
        S, M = len(self.streams), self.max_samples
        values = np.zeros((S, M), np.float32)
        ts = np.zeros((S, M), np.float32)
        valid = np.zeros((S, M), bool)
        for s in range(S):
            recs = self._pending.get(s, [])
            take, keep = [], []
            for r in recs:
                (take if r.timestamp < t_end else keep).append(r)
            self._pending[s] = keep
            take.sort(key=lambda r: r.timestamp)
            if len(take) > M:
                self.stats["overflow"] += len(take) - M
                take = take[-M:]
            for j, r in enumerate(take):
                values[s, j] = r.value
                ts[s, j] = r.timestamp
                valid[s, j] = r.timestamp >= t_start
        return values, ts, valid

    def close_windows(self, bounds):
        K, S, M = len(bounds), len(self.streams), self.max_samples
        values = np.zeros((K, S, M), np.float32)
        ts = np.zeros((K, S, M), np.float32)
        valid = np.zeros((K, S, M), bool)
        for k, (t0, t1) in enumerate(bounds):
            values[k], ts[k], valid[k] = self.close_window(t0, t1)
        return values, ts, valid


def bench_columnar_ingest(quick=False):
    from repro.runtime.accumulator import Accumulator
    from repro.runtime.records import Record, RecordBatch

    K, E, S, M = 32, 8, 8, 64
    per_sw = 16 if quick else 48        # records per (stream, window)
    window_s = 16 * 60.0
    bounds = [(k * window_s, (k + 1) * window_s) for k in range(K)]
    streams = [f"s{i}" for i in range(S)]
    rng = np.random.RandomState(0)

    # one out-of-order record stream per env (same data to both paths)
    n = K * S * per_sw
    sid = np.tile(np.arange(S, dtype=np.int32), n // S)
    ts = rng.uniform(0, K * window_s, n)
    vs = rng.normal(5, 2, n)
    recs = [Record("env", streams[int(s)], float(t), float(v))
            for s, t, v in zip(sid, ts, vs)]
    batch = RecordBatch("env", tuple(streams), sid, ts, vs)

    def run_legacy():
        for _ in range(E):
            acc = _LegacyAccumulator("env", streams, M)
            acc.ingest(recs)
            acc.close_windows(bounds)

    def run_columnar():
        for _ in range(E):
            acc = Accumulator("env", streams, M)
            acc.ingest_batch(batch)
            acc.close_windows(bounds)

    # bit-for-bit parity of the measured paths
    a, b = _LegacyAccumulator("env", streams, M), Accumulator("env", streams, M)
    a.ingest(recs)
    b.ingest_batch(batch)
    ok = all((x == y).all() for x, y in zip(a.close_windows(bounds),
                                            b.close_windows(bounds)))

    reps = 2 if quick else 4
    t_leg = _time(run_legacy, n=reps, warmup=1, best=True)
    t_col = _time(run_columnar, n=reps, warmup=1, best=True)
    total = n * E
    rps_leg = total / (t_leg / 1e6)
    rps_col = total / (t_col / 1e6)
    SUMMARY["records_per_s"]["legacy"] = round(rps_leg, 0)
    SUMMARY["records_per_s"]["columnar"] = round(rps_col, 0)
    SUMMARY["records_per_s"]["speedup"] = round(rps_col / rps_leg, 2)
    SUMMARY["columnar_bit_identical"] = bool(ok)
    _row(f"ingest_legacy_K{K}_E{E}_S{S}", t_leg / total,
         f"{rps_leg:.0f} records/s (per-record loop)")
    _row(f"ingest_columnar_K{K}_E{E}_S{S}", t_col / total,
         f"{rps_col:.0f} records/s | speedup {rps_col / rps_leg:.2f}x | "
         f"bit_identical {ok}")


# --------------------------------------------------------------------------
# Table 3 — per-stage cost + CPU/RSS across stress levels
# --------------------------------------------------------------------------

def bench_stage_breakdown(quick=False):
    import functools

    import jax
    import jax.numpy as jnp
    import psutil

    from repro.core import PipelineConfig
    from repro.core import pipeline as pl
    from repro.core.frame import make_raw_window

    E, S, T, M = (256, 8, 16, 64)
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                         max_samples=M)
    state = pl.init_state(cfg)
    rng = np.random.RandomState(0)
    raw = make_raw_window(rng.normal(5, 2, (E, S, M)).astype(np.float32),
                          rng.uniform(0, T * 60, (E, S, M)).astype(np.float32),
                          rng.rand(E, S, M) > 0.3)
    ws = jnp.zeros((E,), jnp.float32)

    h = jax.jit(functools.partial(pl.stage_harmonize, cfg))
    v, obs, ticks = jax.block_until_ready(h(state, raw, ws))
    a = jax.jit(functools.partial(pl.stage_anomaly, cfg))
    va, oa, rep, na = jax.block_until_ready(a(state, v, obs))
    g = jax.jit(functools.partial(pl.stage_gapfill, cfg))
    vg, fg, ng = jax.block_until_ready(g(state, va, oa, ticks))
    nrm = jax.jit(functools.partial(pl.stage_normalize, cfg))

    proc = psutil.Process()
    _row("stage_harmonize", _time(lambda: jax.block_until_ready(
        h(state, raw, ws))), f"rss {proc.memory_info().rss / 2**20:.0f} MB")
    _row("stage_anomaly", _time(lambda: jax.block_until_ready(
        a(state, v, obs))), "")
    _row("stage_gapfill", _time(lambda: jax.block_until_ready(
        g(state, va, oa, ticks))), "")
    _row("stage_normalize", _time(lambda: jax.block_until_ready(
        nrm(state, vg, oa | fg))), f"cpu {psutil.cpu_percent(0.1):.0f}%")


# --------------------------------------------------------------------------
# Table 4 — deployment strategies: edge (1 env) / fog (32) / cloud (1024)
# --------------------------------------------------------------------------

def bench_deployment(quick=False):
    modes = {"edge": 1, "fog": 32, "cloud": 256 if quick else 1024}
    for name, E in modes.items():
        t = _time(_pipeline(E), n=3 if quick else 6)
        _row(f"deploy_{name}_E{E}", t,
             f"{t / E:.1f} us/env ({E / (t / 1e6):.0f} env-ticks/s)")


# --------------------------------------------------------------------------
# Table 5 — end-to-end serving throughput (Percepta -> LM, batched requests)
# --------------------------------------------------------------------------

def bench_serving(quick=False):
    import jax

    from repro.configs.registry import get_config
    from repro.models import LM
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("qwen3-0.6b:smoke")
    model = LM(cfg, remat_policy="none")
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, batch_slots=4, max_seq=128)
    rng = np.random.RandomState(0)
    n_req = 8 if quick else 16
    reqs = [Request(rid=i, prompt=rng.randint(1, cfg.vocab_size, (8,))
                    .astype(np.int32), max_new_tokens=16)
            for i in range(n_req)]
    t0 = time.time()
    engine.run_until_drained(reqs)
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in reqs)
    _row("serving_engine", dt / max(toks, 1) * 1e6,
         f"{toks / dt:.1f} tok/s | {n_req} reqs on 4 slots | "
         f"{engine.stats['ticks']} ticks")


# --------------------------------------------------------------------------
# Table 6 — Pallas kernels: interpret-mode correctness vs oracle
# --------------------------------------------------------------------------

def bench_kernels(quick=False):
    rng = np.random.RandomState(0)
    from repro.kernels.window_agg.ops import window_agg
    E, S, T = 8, 8, 64
    v = rng.normal(5, 2, (E, S, T)).astype(np.float32)
    m = rng.rand(E, S, T) > 0.3
    mu = rng.normal(5, 1, (E, S)).astype(np.float32)
    var = np.abs(rng.normal(2, .5, (E, S))).astype(np.float32) + .1
    t0 = time.time()
    s1, _ = window_agg(v, m, mu, var, use_pallas=True)
    s2, _ = window_agg(v, m, mu, var, use_pallas=False)
    err = float(np.abs(np.asarray(s1) - np.asarray(s2)).max())
    _row("kernel_window_agg", (time.time() - t0) * 1e6,
         f"max_abs_err {err:.2e} (interpret vs oracle)")

    from repro.kernels.flash_attention.ops import flash_attention
    q = rng.normal(0, 1, (1, 128, 4, 32)).astype(np.float32)
    k = rng.normal(0, 1, (1, 128, 2, 32)).astype(np.float32)
    vv = rng.normal(0, 1, (1, 128, 2, 32)).astype(np.float32)
    t0 = time.time()
    o1 = flash_attention(q, k, vv, use_pallas=True, q_blk=64, kv_blk=64)
    o2 = flash_attention(q, k, vv, use_pallas=False)
    err = float(np.abs(np.asarray(o1) - np.asarray(o2)).max())
    _row("kernel_flash_attention", (time.time() - t0) * 1e6,
         f"max_abs_err {err:.2e}")

    from repro.kernels.rglru_scan.ops import rglru_scan
    a = rng.uniform(.6, .99, (2, 32, 128)).astype(np.float32)
    b = rng.normal(0, .1, (2, 32, 128)).astype(np.float32)
    h0 = np.zeros((2, 128), np.float32)
    t0 = time.time()
    o1, _ = rglru_scan(a, b, h0, use_pallas=True)
    o2, _ = rglru_scan(a, b, h0, use_pallas=False)
    err = float(np.abs(np.asarray(o1) - np.asarray(o2)).max())
    _row("kernel_rglru_scan", (time.time() - t0) * 1e6,
         f"max_abs_err {err:.2e}")

    from repro.kernels.harmonize.ops import harmonize as kharm
    ts = rng.uniform(0, 960, (4, 4, 32)).astype(np.float32)
    vals = rng.normal(0, 1, (4, 4, 32)).astype(np.float32)
    ok = rng.rand(4, 4, 32) > 0.2
    ws = np.zeros((4,), np.float32)
    t0 = time.time()
    o1, _ = kharm(vals, ts, ok, ws, tick_s=60.0, n_ticks=16, use_pallas=True)
    o2, _ = kharm(vals, ts, ok, ws, tick_s=60.0, n_ticks=16, use_pallas=False)
    err = float(np.abs(np.asarray(o1) - np.asarray(o2)).max())
    _row("kernel_harmonize", (time.time() - t0) * 1e6,
         f"max_abs_err {err:.2e}")


# --------------------------------------------------------------------------
# Table 7 — dry-run roofline summary (reads experiments/dryrun/*.json)
# --------------------------------------------------------------------------

def bench_roofline(quick=False):
    import glob
    import json
    import os
    root = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "dryrun")
    cells = []
    for f in sorted(glob.glob(os.path.join(root, "*.json"))):
        d = json.load(open(f))
        if not d.get("skipped") and not d.get("tag"):
            cells.append(d)
    if not cells:
        _row("roofline", 0.0, "no dry-run artifacts (run repro.launch.dryrun)")
        return
    fits = sum(1 for d in cells if d.get("fits_hbm"))
    _row("roofline_cells", 0.0,
         f"{len(cells)} compiled | {fits} fit 16GiB HBM (TPU-adjusted)")
    for d in cells:
        if d["mesh"] != "16x16":
            continue
        _row(f"roofline_{d['arch']}_{d['shape']}",
             max(d["compute_s"], d["memory_s"], d["collective_s"]) * 1e6,
             f"dom={d['dominant']} frac={d['roofline_fraction']:.3f}")


ALL = [bench_ingest, bench_columnar_ingest, bench_ingest_fastpath,
       bench_tick_latency,
       bench_scan_engine, bench_scan_sharded, bench_scan_async,
       bench_predictor_batch, bench_fused_decide, bench_online_train,
       bench_elastic, bench_contract_check, bench_certify, bench_autotune,
       bench_stage_breakdown,
       bench_deployment, bench_serving, bench_kernels, bench_roofline]

# --smoke: the CI-sized subset (Makefile `bench-smoke`) — quick settings:
# tick-latency axes, the scan-engine acceptance cells (incl. the sharded
# mode on the forced host-device mesh, the async overlap cell, the
# batched-Predictor identity cell, the fused-decide cells and the
# elastic slot-pool cells), the autotuner grid, the columnar-ingest
# cell, and the ingest fast-path phase-decomposition cell
SMOKE = [bench_tick_latency, bench_scan_engine, bench_scan_sharded,
         bench_scan_async, bench_predictor_batch, bench_fused_decide,
         bench_online_train, bench_elastic, bench_contract_check,
         bench_certify, bench_autotune, bench_columnar_ingest,
         bench_ingest_fastpath]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI pass: tick latency + scan engines + "
                         "columnar ingest, quick")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="also write rows + windows/s + records/s summary "
                         "to this path (e.g. BENCH_pr2.json)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force an N-device CPU platform "
                         "(--xla_force_host_platform_device_count) so "
                         "scan_sharded runs on a real mesh; must be set "
                         "before JAX initializes")
    args = ap.parse_args()
    if args.host_devices > 0:
        assert "jax" not in sys.modules, \
            "--host-devices must be applied before JAX initializes"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.host_devices}")
    from repro import compat
    compat.enable_compile_cache()
    benches = SMOKE if args.smoke else ALL
    if args.smoke:
        args.quick = True
    # --only accepts "|"- or ","-separated name fragments
    wanted = [w for w in args.only.replace(",", "|").split("|") if w]
    print("name,us_per_call,derived")
    failed = []
    for bench in benches:
        if wanted and not any(w in bench.__name__ for w in wanted):
            continue
        try:
            bench(quick=args.quick)
        except Exception as e:  # a failing table must not hide the others
            _row(bench.__name__, -1.0, f"ERROR {type(e).__name__}: {e}")
            failed.append(bench.__name__)
    if args.json:
        import jax
        out = {
            "bench": "percepta",
            "jax": jax.__version__,
            "devices": len(jax.devices()),
            "quick": bool(args.quick),
            **SUMMARY,
            "rows": RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json}", flush=True)
    if failed:
        print(f"# failed tables: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
