"""Whole runs of the harness on the CPU at a small size, past its look for
a chip, with the timed path broken underneath: each fault has to turn
``correct`` false under every cell's own limits, and a sound run has to
stay true. The control (the reference computed in bfloat16, put in the
program's place) has to fail each cell's limits too."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import check  # noqa: E402
import generator  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
from test_bench_harness import tiny  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _run(name, seed=3):
    cell = spec.load_cell(name, ROOT)
    cell.config = tiny(cell.config, n_envs=8)
    return run.run_cell(cell, seed, 1.0, trace=False, require_tpu=False,
                        log=lambda s: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"
    assert res["device"]["platform"] == "cpu"


def _state_unchanged(monkeypatch):
    from repro.core import pipeline
    tick = pipeline.tick

    def frozen(cfg, state, raw, start):
        _, feats, frame = tick(cfg, state, raw, start)
        return state, feats, frame
    monkeypatch.setattr(pipeline, "tick", frozen)


def _ring_unchanged(monkeypatch):
    from repro.core import replay
    monkeypatch.setattr(replay, "add_batch", lambda buf, *a, **k: buf)


def _half_left_out(monkeypatch):
    import jax.numpy as jnp
    from repro.core import harmonize
    dense = harmonize._harmonize_dense

    def half(values, timestamps, idx, ok, T, agg):
        M = values.shape[-1]
        return dense(values, timestamps, idx,
                     ok & (jnp.arange(M) < M // 2), T, agg)
    monkeypatch.setattr(harmonize, "_harmonize_dense", half)


def _half_minibatch(monkeypatch):
    """The train step's loss is the mean over half of its minibatch."""
    import jax.numpy as jnp
    from repro.core import replay
    sample = replay.sample_device

    def half(buf, rng, batch):
        b = sample(buf, rng, batch)
        return dict(b, valid=b["valid"] & (jnp.arange(batch) < batch // 2))
    monkeypatch.setattr(replay, "sample_device", half)


def _answer_altered(monkeypatch):
    from repro.runtime import predictor
    validate = predictor.validate_actions

    def altered(actions, low, high):
        a, v = validate(actions, low, high)
        return a.at[0, 0].add(0.1), v
    monkeypatch.setattr(predictor, "validate_actions", altered)


FAULTS = {"state_unchanged": _state_unchanged,
          "ring_unchanged": _ring_unchanged,
          "half_left_out": _half_left_out,
          "half_minibatch": _half_minibatch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    FAULTS[fault](monkeypatch)
    res = _run(name)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("name", CELLS)
def test_the_bfloat16_control_is_not_correct(name):
    cell = spec.load_cell(name, ROOT)
    cfg = tiny(cell.config, n_envs=8)
    seed = 2**31 + 77
    ps, ts, ss, _ = generator.seed_words(seed)
    pool = generator.ReadingPool(cfg, seed)
    envs = check.env_sample(cfg["n_envs"], ss)
    traffic = cell.traffic
    ks = ([int(traffic["k"])] * 4 if traffic["load"] == "backlog"
          else [int(traffic["max_k"])] + list(range(1, int(traffic["max_k"]))))
    ref = check.reference_outputs(cfg, pool, ks, envs, ps, ts)
    ctl = check.reference_outputs(cfg, pool, ks, envs, ps, ts,
                                  quantize=reference.bfloat16_round)
    numbers = check.compare(ctl, ref)
    assert numbers["count_mismatch"] == 0      # timestamps stay exact
    assert not check.verdict(numbers, cell.limits), numbers
