"""One run of one benchmark cell on the chip it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's ``PerceptaSystem`` from its configuration file, draws the
readings from ``--seed``, warms up every shape the cell's traffic uses,
drives the traffic for ``--seconds`` (``drive.py``), checks what the timed
path produced against the plain reference (``check.py``), and prints one
JSON line last: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window. Exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))

import check  # noqa: E402
import drive  # noqa: E402
import generator  # noqa: E402
import spec  # noqa: E402
import sut  # noqa: E402
import work  # noqa: E402


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Backend compile seconds, compiles and persistent-cache hits, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits


def devices_for(cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (devices: {devs})")
        if len(devs) < int(cell.entry["chips"]):
            raise NoChip(f"cell {cell.name} asks for {cell.entry['chips']} "
                         f"chips, JAX found {len(devs)}")
    return devs[:int(cell.entry["chips"])]


def enable_cache():
    """The persistent compilation cache at ``<checkout>/.jax_cache``, unless
    ``JAX_COMPILATION_CACHE_DIR`` names another; every program is cached,
    however short its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def warm_up(drv, traffic: dict):
    """Every batch shape the traffic will dispatch, twice over where the
    second dispatch of a shape runs code the first does not (the trainer's
    hot swap)."""
    if traffic["load"] == "backlog":
        ks = [int(traffic["k"])] * 2
    else:
        m = int(traffic["max_k"])
        ks = [m] + list(range(1, m))
    for k in ks:
        drv.run_batch(k)


def latencies_ms(rec: drive.Record, sink, n_envs: int, rate: float):
    """Per env-window of the measured window: transmit time minus the time
    the window's last reading was due, in ms; NaN where not forwarded."""
    out = np.full((rec.windows_due, n_envs), np.nan)
    times = np.asarray(sink.times)
    for i in range(rec.windows_due):
        w = rec.first_window + i
        lo, hi = w * n_envs, (w + 1) * n_envs
        if hi <= times.size:
            out[i] = (times[lo:hi] - (rec.t0 + (i + 1) / rate)) * 1e3
    return out


def attempted_failed(rec: drive.Record, lat, lost: int, n_envs: int):
    """Env-windows attempted in the window and those that failed: never
    forwarded (open loop), or any reading lost on the way."""
    if rec.load == "open":
        attempted = rec.windows_due * n_envs
        forwarded = int(np.isfinite(lat).sum())
    else:
        attempted = forwarded = int(sum(b[2] for b in rec.batches)) * n_envs
    return attempted, attempted - forwarded + int(lost)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, root: str = ROOT,
             t_start: float = None, log=print, control: bool = False) -> dict:
    """Run one cell; returns the result line's object. ``control=True``
    (``calibrate.py``) also holds the bfloat16 control to the reference,
    under the result's ``control`` key."""
    import jax
    t_start = T_START if t_start is None else t_start
    devs = devices_for(cell, require_tpu)
    dev = devs[0]
    if require_tpu:
        enable_cache()
    clock = CompileClock()
    cfg, traffic = cell.config, cell.traffic
    E = int(cfg["n_envs"])
    policy_seed, trainer_seed, sample_seed, _ = generator.seed_words(seed)
    t_import = time.perf_counter()
    pool = generator.ReadingPool(cfg, seed)
    t_data = time.perf_counter()
    sink = sut.ActionSink(int(cfg["n_actions"]))
    with jax.default_device(dev):
        system = sut.build(cfg, policy_seed, trainer_seed, sink)
    t_build = time.perf_counter()
    drv = drive.Load(system, generator.Deliverer(system, pool), traffic,
                       trace=trace)
    c0 = clock.mark()
    with jax.default_device(dev):
        warm_up(drv, traffic)
    t_warm = time.perf_counter()
    c1 = clock.mark()
    trace_dir = os.path.join(root, ".bench_trace", f"{cell.name}-{seed}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans, not every Python call
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.default_device(dev):
        if traffic["load"] == "backlog":
            rec = drv.backlog(seconds)
        else:
            rec = drv.open(seconds)
    if trace:
        jax.profiler.stop_trace()
    c2 = clock.mark()
    setup = {"setup_s": rec.t0 - t_start, "import_s": t_import - t_start,
             "data_s": t_data - t_import, "build_s": t_build - t_data,
             "warmup_s": t_warm - t_build, "compile_s": c1[0] - c0[0],
             "compiles": c1[1] - c0[1], "cache_hits": c1[2] - c0[2],
             "compiles_in_window": c2[1] - c1[1]}
    log(f"setup: {setup['setup_s']:.3f} s = import and backend start "
        f"{setup['import_s']:.3f} "
        f"+ data {setup['data_s']:.3f} + build {setup['build_s']:.3f} "
        f"+ warm-up {setup['warmup_s']:.3f} (backend compile "
        f"{setup['compile_s']:.3f} s in {setup['compiles']} compiles, "
        f"{setup['cache_hits']} persistent-cache hits); compiles inside "
        f"the window: {setup['compiles_in_window']}")
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    envs = check.env_sample(E, sample_seed)
    prog = check.program_outputs(system, sink, drv.rows, envs,
                                 system.window_s)
    ks = list(drv.ks)
    lat = (latencies_ms(rec, sink, E, float(traffic["windows_per_s"]))
           if rec.load == "open" else None)
    system.stop()
    del system, drv
    t_ref = time.perf_counter()
    ref = check.reference_outputs(cfg, pool, ks, envs, policy_seed,
                                  trainer_seed)
    numbers = check.compare(prog, ref)
    t_ref = time.perf_counter() - t_ref
    control_numbers = None
    if control:
        import reference
        ctl = check.reference_outputs(cfg, pool, ks, envs, policy_seed,
                                      trainer_seed,
                                      quantize=reference.bfloat16_round)
        control_numbers = check.compare(ctl, ref)
    correct = check.verdict(numbers, cell.limits)
    trace_red = None
    if trace:
        import trace_reduce
        files = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        try:
            trace_red = trace_reduce.reduce(trace_reduce.load(files[0]))
            log("trace: programs " + json.dumps(
                {k: [round(v["seconds"], 6), v["count"]]
                 for k, v in sorted(trace_red["programs"].items(),
                                    key=lambda kv: -kv[1]["seconds"])[:8]}))
        except (IndexError, ValueError) as exc:
            log(f"trace: no reduction ({exc!r}); trace metrics left out")
        shutil.rmtree(trace_dir, ignore_errors=True)
        if trace_red is not None:
            # the fused program's name is generic (``jit__unknown``): it
            # has to be one program, run once per dispatch, or its time
            # holds some other program's
            _, runs = trace_reduce.program_seconds(
                trace_red, trace_reduce.FUSED_PROGRAM)
            if runs != len(rec.batches):
                raise RuntimeError(
                    f"trace: {runs} runs of {trace_reduce.FUSED_PROGRAM} "
                    f"for {len(rec.batches)} dispatches")
    measured = rec.batches
    windows = int(sum(b[2] for b in measured))
    attempted, failed = attempted_failed(rec, lat, prog.lost, E)
    view = types.SimpleNamespace(
        cell=cell, config=cfg, traffic=traffic, record=rec, setup=setup,
        latencies_ms=lat, trace=trace_red, seconds=seconds,
        window_work=work.window_work(cfg),
        train_work=work.train_step_work(cfg),
        peaks=spec.peaks(dev.device_kind) if require_tpu else None,
        ks=ks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(view)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    log(f"run: {windows} windows in {len(measured)} batches over "
        f"{rec.t_end - rec.t0:.3f} s; reference {t_ref:.3f} s; "
        f"anomalous ticks program {int(prog.anomalous.sum())} reference "
        f"{int(ref.anomalous.sum())}; trainer {prog.extra['train']}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        result["breakdown"] = {"device_ops": trace_red["device_ops"],
                               "idle_gaps": trace_red["idle_gaps"]}
    if control_numbers is not None:
        result["control"] = control_numbers
    result["check"] = {k: {"value": numbers[k],
                           "limit": float(cell.limits[k])}
                       for k in check.NUMBERS}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, ROOT)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          log=lambda s: print(s, file=sys.stderr, flush=True))
    except NoChip as exc:
        print(f"bench/run.py: {exc}", file=sys.stderr)
        return 3
    for name, c in result["check"].items():
        print(f"check: {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check: correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
