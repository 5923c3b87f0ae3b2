"""Host spans of the manager's batch path (``repro.runtime.spans``).

A small ``scan_fused_decide`` system with online training, forwarders and
a LogDB runs two batches under the profiler; the trace is read back with
the benchmark's span loader (``bench/span_reduce.py``). Each span of the
tree occurs once a batch, nested as documented; the assemble counters
match what the queues and the staging buffers hold; and the results are
bit-identical with the profiler on and off. Readings delivered a batch
ahead make the fleet pass carry records, which ``carried`` reports.
"""
import glob
import os
import sys

import jax
import numpy as np
import pytest

from repro.core import PipelineConfig
from repro.core.frame import make_raw_window
from repro.core.reward import energy_reward_spec
from repro.runtime import spans
from repro.runtime.db import LogDB
from repro.runtime.forwarder import Forwarder, ForwarderHub
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.records import RecordBatch
from repro.runtime.system import PerceptaSystem, SourceSpec

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
import span_reduce  # noqa: E402

E, S, M, K = 8, 2, 16, 2

# child -> parent in one batch's tree
TREE = {
    "percepta.batch": "percepta.run_windows",
    "percepta.assemble": "percepta.batch",
    "percepta.dispatch": "percepta.batch",
    "percepta.consume": "percepta.batch",
    "percepta.train.apply": "percepta.dispatch",
    "percepta.fused_step": "percepta.dispatch",
    "percepta.train.dispatch": "percepta.dispatch",
    "percepta.result_wait": "percepta.consume",
    "percepta.forward": "percepta.consume",
    "percepta.log": "percepta.consume",
}


def _system(db_path):
    srcs = [SourceSpec("meter", "mqtt", SimulatedDevice("grid_kw", 60.0,
                                                        base=3.0, seed=1)),
            SourceSpec("price", "http", SimulatedDevice(
                "price_eur", 300.0, base=0.2, amplitude=0.05, seed=2))]
    cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=4, tick_s=60.0,
                         max_samples=M)
    pred = Predictor(linear_policy(2, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     E, cfg.n_features, replay_capacity=16)
    hub = ForwarderHub([Forwarder("hvac", "mqtt", [0]),
                        Forwarder("ev", "amqp", [1])])
    return PerceptaSystem([f"b{i}" for i in range(E)], srcs, cfg, pred,
                          forwarders=hub, db=LogDB(db_path, salt="x"),
                          speedup=5000.0, manual_time=True,
                          mode="scan_fused_decide", scan_k=K, train="online",
                          train_cfg={"batch_size": 4, "seed": 0})


def _dequeued(system):
    return sum(q["dequeued"] for q in system.broker.stats().values())


def _deliver_ahead(system):
    """One sorted batch of env b0's grid readings for the window after the
    next batch, published straight to its queue: the next batch carries
    it, the one after takes it."""
    t0 = system.window_bounds(system.window_index + K)[0]
    ts = t0 + np.arange(6) * 10.0
    system.broker.publish(RecordBatch.from_columns("b0", "grid_kw", ts,
                                                   ts / 100.0, True))


def _run(tmp_path, name, trace):
    """Warm up one batch, then run two more (traced when ``trace``), each
    with readings delivered ahead. Returns the rows, the sinks, the
    policy, the dequeued count before and after each traced batch, the
    trace's spans, and the fleet's carried records and sort fallback after
    each traced batch."""
    system = _system(str(tmp_path / f"{name}.db"))
    rows = system.run_windows(K)
    dequeued = [_dequeued(system)]
    fleet = []
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / name), profiler_options=opts)
        assert spans.tracing()
    try:
        for _ in range(2):
            _deliver_ahead(system)
            rows += system.run_windows(K)
            dequeued.append(_dequeued(system))
            fleet.append((system.fleet.pending(),
                          system.fleet.sort_fallback))
    finally:
        if trace:
            jax.profiler.stop_trace()
    found = []
    if trace:
        path, = glob.glob(str(tmp_path / name / "**" / "*.xplane.pb"),
                          recursive=True)
        found = span_reduce.load_spans(path)
    sinks = [list(f.sink) for f in system.forwarders.forwarders]
    policy = jax.tree.map(np.asarray, system.snapshot_policy())
    system.stop()
    return rows, sinks, policy, dequeued, found, fleet


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("spans"), "on", trace=True)


def test_tracing_is_false_outside_a_session():
    assert spans.tracing() is False
    with spans.span("percepta.test", n=1):      # a no-op without a session
        assert spans.tracing() is False


def test_each_span_once_per_batch_and_nested(traced):
    found = traced[4]
    by_name = {}
    for x in found:
        by_name.setdefault(x.name, []).append(x)
    assert set(by_name) == set(TREE) | {"percepta.run_windows"}
    for name, evs in by_name.items():
        assert len(evs) == 2, name
    assert len({x.thread for x in found}) == 1
    for child, parent in TREE.items():
        for c, p in zip(sorted(by_name[child], key=lambda x: x.start),
                        sorted(by_name[parent], key=lambda x: x.start)):
            assert p.start <= c.start and c.end <= p.end, (child, parent)
    batches = sorted(by_name["percepta.batch"], key=lambda x: x.start)
    assert [b.meta["k"] for b in batches] == [K, K]
    assert [b.meta["window"] for b in batches] == [K, 2 * K]


def test_assemble_counters(traced):
    found, dequeued, fleet = traced[4], traced[3], traced[5]
    assemble = sorted((x for x in found if x.name == "percepta.assemble"),
                      key=lambda x: x.start)
    assert [(a.meta["carried"], a.meta["sort_fallbacks"])
            for a in assemble] == fleet
    assert fleet[0][0] >= 6            # the readings delivered ahead
    for j, a in enumerate(assemble):
        m = a.meta
        assert m["records"] == dequeued[j + 1] - dequeued[j] > 0
        assert m["envs"] == E
        assert m["staged_bytes"] == K * E * S * M * 9
        assert m["queue_wait_ms"] >= 0
        for key in ("drain_ms", "ingest_ms", "close_ms"):
            assert m[key] >= 0
        assert m["drain_ms"] + m["ingest_ms"] + m["close_ms"] \
            <= 1e3 * (a.end - a.start) * 1e-9


def test_profiler_leaves_results_bit_identical(traced, tmp_path):
    rows, sinks, policy, dequeued, _, fleet = _run(tmp_path, "off",
                                                   trace=False)
    strip = lambda rs: [{k: v for k, v in r.items() if k != "latency_s"}
                        for r in rs]
    assert strip(rows) == strip(traced[0])
    assert sinks == traced[1]
    assert dequeued == traced[3]
    assert fleet == traced[5]
    for a, b in zip(jax.tree.leaves(policy), jax.tree.leaves(traced[2])):
        np.testing.assert_array_equal(a, b)


def test_untraced_assembly_computes_no_counter(tmp_path, monkeypatch):
    """Without a profiler session the fleet's counters are never read."""
    system = _system(str(tmp_path / "quiet.db"))

    def boom():
        raise AssertionError("counter computed while not tracing")

    monkeypatch.setattr(system.fleet, "pending", boom)
    assert not spans.tracing()
    _deliver_ahead(system)
    assert len(system.run_windows(K)) == K
    system.stop()


def test_fused_program_is_named(tmp_path):
    system = _system(str(tmp_path / "name.db"))
    z = np.zeros((K, E, S, M), np.float32)
    raw = make_raw_window(z, z, np.zeros((K, E, S, M), bool))
    starts = np.zeros((K, E), np.float32)
    text = system.pipeline._scan.lower(system.state, system._dstate, raw,
                                       starts).compile().as_text()
    module = text.splitlines()[0]
    assert "run_many_decide" in module and "_unknown" not in module
    system.stop()


def test_queue_stamps_the_first_put_after_a_drain():
    from repro.runtime.queues import EnvQueue
    from repro.runtime.records import Record
    q = EnvQueue("e")
    assert q.drain() == [] and q.drained_since is None
    q.put(Record("e", "s", 1.0, 1.0))
    first = q._first_put
    q.put(Record("e", "s", 2.0, 2.0))
    assert q._first_put == first          # stamped once, when it filled
    assert len(q.drain()) == 2 and q.drained_since == first
    assert q.drain() == [] and q.drained_since is None
    assert "since" not in str(q.stats)
