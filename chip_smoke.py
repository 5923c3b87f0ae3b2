"""Smoke run of Percepta's served path on a TPU, through its entry points.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the env-sharded engine, four chips

The deployment is a fog/cloud node at the size its operators run (ROADMAP
W3): E = 1024 environments x S = 8 sensor streams reporting every 30 s,
60 s or 300 s, one-hour windows of T = 60 one-minute ticks, M = 64 raw
samples per stream and window, K = 8 windows per device dispatch, an
MLP policy (hidden 128) deciding A = 4 actions per environment, a
device-resident replay ring of 8192 transitions per environment and
online training (``mode="scan_fused_decide"``, ``train="online"``).
Readings are generated from a seed, with each ``SimulatedDevice``'s
interval, signal, noise, dropout and spike settings, and published to the
system's broker as ``RecordBatch`` columns; everything from the queues on
is the normal path (``PerceptaSystem.run_windows``).

One chip, three phases:

  * ``main``: one warm-up batch and four measured batches on the TPU,
    then the same system built on the host CPU backend in this process,
    fed the same readings. Record, observed, filled and anomalous counts
    must match exactly; actions (as forwarded), rewards and the exported
    replay ring within ``RTOL``/``ATOL`` (see ``_close``).
  * ``kernels``: the same deployment with the Pallas kernels on
    (``PipelineConfig(use_pallas=True, feature_agg="mean")`` and the
    rglru policy with ``use_pallas=True``) against the same build with
    them off, both on the TPU; the compiled program must hold each of the
    ``locf``, ``window_agg`` and ``rglru_scan`` kernels as a
    ``tpu_custom_call``.

``--chips 4`` runs only the env-sharded engine
(``scan_fused_decide_sharded``, a {data: 4} mesh) against
``scan_fused_decide`` on one chip of the same host, and prints the bytes
the carry and the replay ring hold on each device.

Per-batch wall times printed here are smoke readings, not benchmark
metrics. The script refuses to run without a TPU (JAX falls back to the
CPU without complaint when the TPU backend fails to start), exits
non-zero when any phase fails, and prints its JSON result line only when
every phase passed.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import struct
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# --- the deployment --------------------------------------------------------
E, S, T, TICK_S, M, K, A = 1024, 8, 60, 60.0, 64, 8, 4
CAPACITY, HIDDEN, SEED = 8192, 128, 0
MEASURED_BATCHES = 4
# (stream, interval_s, base, amplitude): the mixed 30 s / 60 s / 300 s
# sources of examples/serve_edge.py, repeated over eight streams
STREAMS = [("grid_kw", 60.0, 3.0, 2.0), ("price_eur", 300.0, 0.2, 0.05),
           ("temp_c", 30.0, 21.0, 1.5), ("pv_kw", 60.0, 2.0, 2.0),
           ("hum_pct", 300.0, 45.0, 5.0), ("co2_ppm", 30.0, 600.0, 80.0),
           ("flow_lps", 60.0, 1.0, 0.3), ("occ", 300.0, 10.0, 4.0)]

# Tolerance of the TPU-vs-CPU comparison, per element:
# |tpu - cpu| <= ATOL + RTOL * |cpu|. Both backends compute in float32
# (unit roundoff 6e-8) but differ in the implementations of exp/tanh/
# sqrt/division (a few ulp), in reduction order and in FMA contraction.
# The observations are z-scores, (v - mean) / sigma, so they resolve a
# stream at level L only to ~ulp(L) / sigma: moving every reading by one
# float32 ulp moves them by up to ~1.5e-5 on the CPU alone, and the
# policy's gain (up to ~10 at these weights) carries that into the
# actions. ATOL = RTOL = 1e-4 holds every output to that float32
# resolution; inputs rounded to bfloat16 (8-bit mantissa, 4e-3
# relative) moved served rewards by 6.4e-4 relative on a v5e and fail
# it. Counts are integers and must match exactly.
RTOL, ATOL = 1e-4, 1e-4

KERNELS = ("locf", "window_agg", "rglru_scan")


def _require_tpu():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (devices: {devices}); "
                 "this smoke run measures nothing on another backend")
    return devices


# --- building and feeding the deployment ------------------------------------

def _sources():
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import SourceSpec
    return [SourceSpec(name, "amqp",
                       SimulatedDevice(name, interval, base=base,
                                       amplitude=amp, seed=i))
            for i, (name, interval, base, amp) in enumerate(STREAMS)]


class _ActionSink:
    """Collects the forwarded action payloads (amqp: name, t, value)."""

    def __init__(self):
        self.payloads = []

    def values(self, n_windows: int, n_envs: int) -> np.ndarray:
        v = np.array([struct.unpack("<d", p[40:48])[0]
                      for p in self.payloads])
        return v.reshape(n_windows, n_envs, A)


def build(device, *, n_envs=E, capacity=CAPACITY, mode="scan_fused_decide",
          use_pallas=False, feature_agg="last", policy=None, train="online"):
    """The smoke deployment as a user wires it, placed on ``device``."""
    import jax

    from repro.core import PipelineConfig
    from repro.core.reward import energy_reward_spec
    from repro.runtime.forwarder import Forwarder, ForwarderHub
    from repro.runtime.policies import PolicyConfig, build_policy
    from repro.runtime.predictor import ActionSpace, Predictor
    from repro.runtime.system import PerceptaSystem

    cfg = PipelineConfig(n_envs=n_envs, n_streams=S, n_ticks=T,
                         tick_s=TICK_S, max_samples=M,
                         use_pallas=use_pallas, feature_agg=feature_agg)
    sink = _ActionSink()
    with jax.default_device(device):
        model = build_policy(
            policy or PolicyConfig("mlp", {"hidden": HIDDEN}),
            cfg.n_features, A, n_envs)
        pred = Predictor(model,
                         energy_reward_spec(price_idx=1, grid_idx=0,
                                            temp_idx=2),
                         ActionSpace(-np.ones(A), np.ones(A)), n_envs,
                         cfg.n_features, replay_capacity=capacity)
        hub = ForwarderHub([Forwarder("actuators", "amqp", range(A),
                                      transmit=sink.payloads.append)])
        system = PerceptaSystem([f"env-{i:04d}" for i in range(n_envs)],
                                _sources(), cfg, pred, forwarders=hub,
                                mode=mode, scan_k=K, manual_time=True,
                                train=train)
    return system, sink


def publish_batch(system, batch: int, seed: int = SEED) -> None:
    """Publish batch ``batch``'s readings (K windows, every env and stream)
    to ``system``'s broker, generated from ``seed`` with each source's
    SimulatedDevice settings."""
    from repro.runtime.records import RecordBatch
    rng = np.random.default_rng([seed, batch])
    start = batch * K * system.window_s
    end = start + K * system.window_s
    n_envs = len(system.env_ids)
    for src in system.sources:
        d = src.device
        t = np.arange(math.ceil(start / d.interval_s),
                      math.ceil(end / d.interval_s)) * d.interval_s
        shape = (n_envs, t.size)
        ts = t[None, :] + rng.uniform(0.0, d.jitter_s, shape)
        v = (d.base + d.amplitude * np.sin(2 * np.pi * t / d.period_s)[None]
             + rng.normal(0.0, d.noise, shape))
        spikes = rng.random(shape) < d.spike_p
        v = v + spikes * d.spike_scale * rng.choice([-1.0, 1.0], shape)
        keep = rng.random(shape) >= d.dropout_p
        for e, env in enumerate(system.env_ids):
            k = keep[e]
            system.broker.publish(RecordBatch.from_columns(
                env, d.stream, ts[e, k], v[e, k], sorted_ts=True))


class _CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit records its retrieval time)."""

    def __init__(self):
        import jax
        self.seconds, self.cache_hits = 0.0, 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.seconds, self.cache_hits


def run(system, device, batches, clock=None, label=""):
    """Feed and run ``batches`` K-window batches; returns the result rows
    and per-batch wall seconds (run_windows only, publishing excluded)."""
    import jax
    rows, walls = [], []
    for b in batches:
        publish_batch(system, b)
        c0 = clock.mark() if clock else None
        t0 = time.perf_counter()
        with jax.default_device(device):
            rows += system.run_windows(K, pump=False)
        walls.append(time.perf_counter() - t0)
        if clock is not None and b == batches[0]:
            dt, hits = (clock.seconds - c0[0], clock.cache_hits - c0[1])
            print(f"{label}: compile_s {dt:.3f} (backend compile seconds "
                  f"in the first batch; persistent-cache hits {hits})",
                  flush=True)
    return rows, walls


# --- comparison --------------------------------------------------------------

def _close(name, got, ref, report, failures):
    """Records max |got - ref|; a failure unless every element is finite
    and within ATOL + RTOL * |ref|."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        failures.append(f"{name}: shape {got.shape} vs {ref.shape}")
        return
    err = np.abs(got - ref)
    report[name] = float(err.max()) if err.size else 0.0
    bad = ~(err <= ATOL + RTOL * np.abs(ref))       # NaN counts as bad
    if bad.any():
        i = np.unravel_index(np.argmax(bad), err.shape)
        failures.append(f"{name}: {int(bad.sum())} of {err.size} elements "
                        f"outside atol {ATOL} + rtol {RTOL}; first at {i}: "
                        f"{got[i]!r} vs {ref[i]!r}")


def _exact(name, got, ref, failures):
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape or not (got == ref).all():
        failures.append(f"{name}: differs")


def compare(label, sys_a, sink_a, rows_a, sys_b, sink_b, rows_b):
    """Hold system A to system B: exact counts, close floats. Prints the
    max error of every float output, then fails on any mismatch."""
    report, count_fails, float_fails = {}, [], []
    for key in ("records", "observed_frac", "filled_frac", "anomalous"):
        _exact(key, [r[key] for r in rows_a], [r[key] for r in rows_b],
               count_fails)
    n_win, n_envs = len(rows_a), len(sys_a.env_ids)
    _close("mean_reward", [r["mean_reward"] for r in rows_a],
           [r["mean_reward"] for r in rows_b], report, float_fails)
    _close("actions", sink_a.values(n_win, n_envs),
           sink_b.values(n_win, n_envs), report, float_fails)
    n = sys_a.replay_size()
    _exact("replay_size", n, sys_b.replay_size(), count_fails)
    ra, rb = sys_a.export_replay("smoke"), sys_b.export_replay("smoke")
    for key in ("tick_idx", "version", "valid", "times"):
        _exact(f"replay.{key}", ra[key][:, :n], rb[key][:, :n], count_fails)
    for key in ("obs", "actions", "rewards", "next_obs"):
        _close(f"replay.{key}", ra[key][:, :n], rb[key][:, :n], report,
               float_fails)
    print(f"{label}: counts exact {not count_fails}; max |error| "
          + " ".join(f"{k} {v:.3e}" for k, v in report.items())
          + f" (tolerance atol {ATOL} + rtol {RTOL}; {n_win} windows, "
          f"{n} replay rows)", flush=True)
    if count_fails or float_fails:
        raise AssertionError(f"{label}: "
                             + "; ".join(count_fails + float_fails))


# --- phases -------------------------------------------------------------------

def phase_main(tpu, cpu, clock, n_envs=E, capacity=CAPACITY):
    """TPU run (warm-up + measured batches) held to a CPU run of the same
    system and readings."""
    batches = list(range(1 + MEASURED_BATCHES))
    sys_t, sink_t = build(tpu, n_envs=n_envs, capacity=capacity)
    rows_t, walls = run(sys_t, tpu, batches, clock, "main")
    print("main: per-batch wall s (smoke reading, not a benchmark metric) "
          f"warm-up {walls[0]:.3f} measured "
          + " ".join(f"{w:.3f}" for w in walls[1:]), flush=True)
    stats = tpu.memory_stats() or {}
    print(f"main: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"(device {tpu.device_kind}); trainer {sys_t.train_stats()}",
          flush=True)
    sys_c, sink_c = build(cpu, n_envs=n_envs, capacity=capacity)
    rows_c, walls_c = run(sys_c, cpu, batches)
    print("main: cpu reference per-batch wall s "
          + " ".join(f"{w:.3f}" for w in walls_c), flush=True)
    try:
        compare("main", sys_t, sink_t, rows_t, sys_c, sink_c, rows_c)
    finally:
        sys_t.stop(), sys_c.stop()


def _compiled_text(system, device):
    """HLO text of the system's fused window->decide->bank program,
    compiled for ``device`` at the shapes the system dispatches."""
    import jax
    import jax.numpy as jnp

    from repro.core.frame import RawWindow
    from repro.core.pipeline import run_many_decide

    n_envs = system.cfg.n_envs
    with jax.default_device(device):
        fn = jax.jit(functools.partial(run_many_decide, system.cfg,
                                       system.predictor.make_decide_fn()))
        state, dstate = jax.eval_shape(
            lambda: (system.snapshot_state(), system.snapshot_decide()))
        sds = lambda dt: jax.ShapeDtypeStruct((K, n_envs, S, M), dt)
        raw = RawWindow(sds(jnp.float32), sds(jnp.float32), sds(jnp.bool_))
        starts = jax.ShapeDtypeStruct((K, n_envs), jnp.float32)
        return fn.lower(state, dstate, raw, starts).compile().as_text()


def kernel_lowering(text: str) -> dict:
    """Kernel name -> whether the compiled text holds it as a
    ``tpu_custom_call``."""
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    return {k: any(f"%{k}" in ln for ln in lines) for k in KERNELS}


def phase_kernels(tpu, n_envs=E, capacity=CAPACITY):
    """Pallas kernels on vs off, both on the TPU."""
    from repro.runtime.policies import PolicyConfig
    out = {}
    for use_pallas in (True, False):
        system, sink = build(
            tpu, n_envs=n_envs, capacity=capacity, use_pallas=use_pallas,
            feature_agg="mean", train=None,
            policy=PolicyConfig("rglru", {"hidden": HIDDEN,
                                          "use_pallas": use_pallas}))
        rows, _ = run(system, tpu, [0, 1])
        out[use_pallas] = (system, sink, rows)
    sys_p, sink_p, rows_p = out[True]
    sys_x, sink_x, rows_x = out[False]
    try:
        lowered = kernel_lowering(_compiled_text(sys_p, tpu))
        print("kernels: tpu_custom_call " + " ".join(
            f"{k}={v}" for k, v in lowered.items()), flush=True)
        missing = [k for k, v in lowered.items() if not v]
        if missing:
            raise AssertionError(f"kernels not lowered to tpu_custom_call: "
                                 f"{missing}")
        compare("kernels", sys_p, sink_p, rows_p, sys_x, sink_x, rows_x)
    finally:
        sys_p.stop(), sys_x.stop()


def _bytes_per_device(tree) -> dict:
    import jax
    out = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return dict(sorted(out.items()))


def phase_sharded(devices, n_envs=E, capacity=CAPACITY):
    """scan_fused_decide_sharded on every device vs scan_fused_decide on
    the first."""
    import jax
    batches = list(range(1 + MEASURED_BATCHES))
    sys_s, sink_s = build(devices[0], n_envs=n_envs, capacity=capacity,
                          mode="scan_fused_decide_sharded")
    mesh = dict(sys_s.pipeline.mesh.shape)
    rows_s, walls = run(sys_s, devices[0], batches)
    print(f"sharded: mesh {mesh}; per-batch wall s (smoke reading, not a "
          "benchmark metric) warm-up "
          f"{walls[0]:.3f} measured " + " ".join(f"{w:.3f}"
                                                 for w in walls[1:]),
          flush=True)
    carry = _bytes_per_device((sys_s.snapshot_state(),
                               sys_s.snapshot_decide()))
    ring = _bytes_per_device(sys_s.snapshot_decide().replay)
    print(f"sharded: carry bytes per device {carry}; replay ring bytes "
          f"per device {ring}", flush=True)
    print("sharded: bytes_in_use per device " + str(
        {d.id: (d.memory_stats() or {}).get("bytes_in_use")
         for d in jax.devices()}), flush=True)
    if len(ring) != len(devices) or len(set(ring.values())) != 1:
        raise AssertionError(f"replay ring not split evenly over "
                             f"{len(devices)} devices: {ring}")
    sys_1, sink_1 = build(devices[0], n_envs=n_envs, capacity=capacity)
    rows_1, walls_1 = run(sys_1, devices[0], batches)
    print("sharded: one-chip scan_fused_decide per-batch wall s "
          + " ".join(f"{w:.3f}" for w in walls_1), flush=True)
    try:
        compare("sharded", sys_s, sink_s, rows_s, sys_1, sink_1, rows_1)
    finally:
        sys_s.stop(), sys_1.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the env-sharded engine on a "
                         "four-chip host against one chip of it")
    args = ap.parse_args(argv)
    devices = _require_tpu()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compat
    compat.enable_compile_cache()
    import jax

    tpu = devices[0]
    print(f"device: {tpu.platform} {tpu.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}", flush=True)
    if args.chips == 4:
        if len(devices) < 4:
            sys.exit(f"chip_smoke --chips 4: {len(devices)} device(s)")
        phases = {"sharded": lambda: phase_sharded(devices[:4])}
    else:
        clock = _CompileClock()
        cpu = jax.devices("cpu")[0]
        phases = {"main": lambda: phase_main(tpu, cpu, clock),
                  "kernels": lambda: phase_kernels(tpu)}
    failed = []
    for name, phase in phases.items():
        t0 = time.perf_counter()
        try:
            phase()
            print(f"{name}: passed in {time.perf_counter() - t0:.1f} s",
                  flush=True)
        except Exception:
            traceback.print_exc()
            print(f"{name}: FAILED", flush=True)
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": tpu.platform, "kind": tpu.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
