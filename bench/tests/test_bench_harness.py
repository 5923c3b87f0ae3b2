"""The harness itself, on the CPU: pieces found by name, the generator's
determinism, the latency and rate arithmetic, and the comparison that
decides ``correct``."""
import json
import os
import shutil
import sys
from array import array

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import check  # noqa: E402
import drive  # noqa: E402
import generator  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny(config: dict, n_envs: int = 4) -> dict:
    cfg = json.loads(json.dumps(config))
    periods = {st["period_s"] for st in cfg["streams"]}
    W = cfg["n_ticks"] * cfg["tick_s"]
    cfg.update(n_envs=n_envs, replay_capacity=64,
               data_pool_windows=int(max(periods) // W))
    return cfg


@pytest.mark.parametrize("name", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_is_found_by_name(name):
    cell = spec.load_cell(name, ROOT)
    assert cell.config["name"] == cell.entry["config"]
    assert cell.traffic["load"] in ("backlog", "open")
    assert set(check.NUMBERS) <= set(cell.limits)
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.read)
        assert m.entry.get("moves", m.name) in names | {m.name}


def test_a_new_metric_file_is_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = _spec()
    cell = data["workloads"][0]["name"]
    data["per_layer"].append({
        "name": "probe_count.backlog", "unit": "windows", "better": "higher",
        "source": "program_counter", "layer": "manager",
        "moves": "env_windows_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    (root / "bench" / "metrics" / "probe_count.backlog.py").write_text(
        "def read(run):\n    return 42\n")
    loaded = spec.load_cell(cell, str(root))
    probe = [m for m in loaded.per_layer if m.name == "probe_count.backlog"]
    assert probe and probe[0].read(None) == 42


def test_generator_is_deterministic_per_seed():
    cfg = tiny(spec.load_cell("fog_energy_e1k.backlog", ROOT).config)
    seed = 2**31 + 12345
    a, b = generator.ReadingPool(cfg, seed), generator.ReadingPool(cfg, seed)
    c = generator.ReadingPool(cfg, seed + 1)
    for p in range(cfg["data_pool_windows"]):
        for s in range(len(cfg["streams"])):
            x, y = a.blocks[p][s], b.blocks[p][s]
            assert np.array_equal(x.ts, y.ts)
            assert np.array_equal(x.values, y.values)
            assert np.array_equal(x.offsets, y.offsets)
    assert not np.array_equal(a.blocks[0][0].values, c.blocks[0][0].values)
    # every reading of window w lies inside it, at millisecond jitter
    blk = a.block(3, 2)
    assert blk.ts.min() >= 0.01 and blk.ts.max() < a.window_s
    assert generator.seed_words(seed) == generator.seed_words(seed)


def test_latency_rate_and_failures_on_a_synthetic_schedule():
    E, rate = 2, 2.0
    rec = drive.Record("open", E, t0=100.0, t_end=101.5, first_window=2,
                       windows_due=3)
    sink = type("Sink", (), {})()
    # windows 0, 1 (warm-up) and 2, 3 forwarded; window 4 never
    sink.times = array("d", [0, 0, 0, 0,
                             100.6, 100.7,      # due 100.5
                             101.25, 101.3])    # due 101.0
    lat = run.latencies_ms(rec, sink, E, rate)
    assert np.allclose(lat[:2], [[100, 200], [250, 300]])
    assert np.isnan(lat[2]).all()
    attempted, failed = run.attempted_failed(rec, lat, 0, E)
    assert (attempted, failed) == (6, 2)
    view = type("View", (), {"latencies_ms": lat})()
    p50 = spec.load_reader("action_latency_p50_ms")(view)
    assert p50 == pytest.approx(225.0)
    p95 = spec.load_reader("action_latency_p95_ms.open")(view)
    assert p95 == pytest.approx(292.5)
    # backlog: windows of the batches over the bracketing completions
    rec = drive.Record("backlog", 4, t0=10.0, t_end=12.5,
                       batches=[(10.0, 11.0, 8, 16), (11.0, 12.5, 8, 24)])
    view = type("View", (), {"record": rec})()
    assert spec.load_reader("env_windows_per_s")(view) == pytest.approx(
        16 * 4 / 2.5)
    assert run.attempted_failed(rec, None, 3, 4) == (64, 3)


@pytest.fixture(scope="module")
def reference_run():
    cfg = tiny(spec.load_cell("fog_energy_e1k.backlog", ROOT).config)
    pool = generator.ReadingPool(cfg, 5)
    envs = check.env_sample(cfg["n_envs"], 9)
    out = check.reference_outputs(cfg, pool, [3, 2], envs, 11, 13)
    return cfg, pool, envs, out


def test_the_check_passes_the_same_outputs(reference_run):
    cfg, pool, envs, ref = reference_run
    again = check.reference_outputs(cfg, pool, [3, 2], envs, 11, 13)
    numbers = check.compare(again, ref)
    assert all(v == 0 for v in numbers.values())
    limits = {k: (0 if k == "count_mismatch" else 1e-4)
              for k in check.NUMBERS}
    assert check.verdict(numbers, limits)


@pytest.mark.parametrize("fault", ["action", "observed", "replay", "policy",
                                   "reward", "lost", "missing"])
def test_the_check_fails_a_perturbed_output(reference_run, fault):
    import copy
    cfg, pool, envs, ref = reference_run
    bad = copy.deepcopy(ref)
    if fault == "action":
        bad.actions[2, 1, 0] += 1e-3
    elif fault == "observed":
        bad.observed[3] -= 1
    elif fault == "replay":
        bad.replay["obs"][0, 1, 0] += 0.1 * np.abs(bad.replay["obs"]).max()
    elif fault == "policy":
        bad.served_policy["w2"] = bad.served_policy["w2"] * 1.01
    elif fault == "reward":
        bad.mean_reward[1] += 0.1 * np.abs(bad.mean_reward).mean()
    elif fault == "lost":
        bad.lost = 1
    elif fault == "missing":
        bad.actions = bad.actions[:-1]
        bad.observed, bad.filled = bad.observed[:-1], bad.filled[:-1]
    numbers = check.compare(bad, ref)
    limits = {k: (0 if k == "count_mismatch" else 1e-4)
              for k in check.NUMBERS}
    assert not check.verdict(numbers, limits), numbers
