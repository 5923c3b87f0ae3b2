"""Host ingest fast path: bit-identity with the legacy path everywhere.

The fleet-wide assembly pass (``ingest_fastpath=True``, the default) must
reproduce the per-env legacy chunk-list + global-lexsort Accumulators bit
for bit: windows, stats, tie order, overflow truncation, and the
replay/LogDB sinks — across codecs, ``ingest="records"`` vs
``"columnar"``, elastic masked pools, and scan/async/fused modes.
"""
import numpy as np
import pytest

from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.accumulator import Accumulator, FleetAccumulator
from repro.runtime.db import LogDB
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.queues import _head
from repro.runtime.receivers import Receiver, SimulatedDevice
from repro.runtime.records import Record, RecordBatch
from repro.runtime.system import PerceptaSystem, SourceSpec, _window_counts
from repro.runtime.translator import Translator
from repro.testing import given, settings, st

STREAMS = ["grid_kw", "temp_c", "price"]
BOUNDS = [(0.0, 100.0), (100.0, 200.0), (200.0, 300.0)]
LATER_BOUNDS = [(300.0, 400.0), (400.0, 500.0)]


def _mixed_items(rng, n=150, max_t=480.0):
    """A drained-queue mix: singles, multi-stream batches, single-stream
    sorted and unsorted batches — with boundary ties, stale records and
    rows past the last window end (stay pending)."""
    items, recs = [], []
    for i in range(n):
        s = STREAMS[rng.randint(len(STREAMS))]
        t = float(rng.uniform(0, max_t))
        if i % 13 == 0:                       # exact boundary ties
            t = float(BOUNDS[i % 3][1])
        recs.append(Record("env", s, t, float(rng.normal(5, 2))))
    i = 0
    while i < len(recs):
        kind = rng.randint(4)
        take = recs[i:i + 1 + rng.randint(12)]
        i += len(take)
        if kind == 0:
            items.extend(take)                # singles
        elif kind == 1:
            items.append(RecordBatch.from_records(take))   # multi-stream
        else:
            s = take[0].stream                # single-stream batch
            ts = np.asarray([r.timestamp for r in take])
            vs = np.asarray([r.value for r in take])
            if kind == 2:                     # sorted + honestly flagged
                order = np.argsort(ts, kind="stable")
                ts, vs = ts[order], vs[order]
                items.append(RecordBatch.from_columns("env", s, ts, vs,
                                                      sorted_ts=True))
            else:                             # arrival order, unflagged
                items.append(RecordBatch.from_columns("env", s, ts, vs))
    return items


def _close_twice(acc):
    """Two close rounds: the second exercises the retained tail and fresh
    stats accumulation."""
    r1 = acc.close_windows(BOUNDS, rebase=True)
    r2 = acc.close_windows(LATER_BOUNDS, rebase=True)
    return r1, r2


def _new_stats():
    return {"records": 0, "unknown_stream": 0, "overflow": 0}


def _fleet_close_twice(items, max_samples):
    """``_close_twice`` through the fleet pass, for one env in slot 0:
    the two rounds' (K, S, M) triples and the env's stats."""
    fleet = FleetAccumulator(STREAMS, max_samples)
    stats = {0: _new_stats()}
    fleet.ingest([(0, items)], np.asarray([b[0] for b in BOUNDS]), stats)
    rounds = []
    for bounds in (BOUNDS, LATER_BOUNDS):
        out = _zeros(len(bounds), 1, max_samples)
        fleet.close_windows(bounds, out, stats)
        rounds.append(tuple(a[:, 0] for a in out))
    return rounds, stats[0]


@pytest.mark.parametrize("max_samples", [4, 16])   # 4 forces overflow
def test_fleet_pass_equals_lexsort_bit_for_bit(rng, max_samples):
    items = _mixed_items(rng)
    slow = Accumulator("env", STREAMS, max_samples)
    slow.ingest(items)
    rounds, stats = _fleet_close_twice(items, max_samples)
    for ra, rb in zip(rounds, _close_twice(slow)):
        for x, y in zip(ra, rb):
            assert x.dtype == y.dtype and (x == y).all()
    assert stats == slow.stats


@given(seed=st.integers(0, 10_000), max_samples=st.sampled_from((3, 8, 64)))
@settings(max_examples=25, deadline=None)
def test_property_fleet_pass_vs_lexsort_parity(seed, max_samples):
    """Random record streams (random batching, sortedness, ties, overflow):
    the fleet pass and the global-lexsort close agree bit for bit on every
    output array and every stat, across two close rounds."""
    rng = np.random.RandomState(seed)
    items = _mixed_items(rng, n=30 + rng.randint(120))
    slow = Accumulator("env", STREAMS, max_samples)
    slow.ingest(items)
    rounds, stats = _fleet_close_twice(items, max_samples)
    for ra, rb in zip(rounds, _close_twice(slow)):
        for x, y in zip(ra, rb):
            assert x.dtype == y.dtype and (x == y).all()
    assert stats == slow.stats


@pytest.mark.parametrize("arrival,how", [
    ("env_major", "close_fast"),     # one run per (window, env, stream)
    ("env_reversed", "close_fast"),  # runs need not be in key order
    ("interleaved", "close_sort"),   # a group split over runs
])
def test_grouped_input_closes_without_timestamp_sort(arrival, how):
    """Time-ordered input never takes the timestamp sort: rows already
    grouped by staging row close with no sort at all, split groups with
    one stable sort by group; the staging bytes are the same."""
    E, M = 3, 8
    ts = np.arange(6, dtype=np.float64) * 15.0
    items = {e: [RecordBatch.from_columns(f"e{e}", "temp_c", ts, ts + e)]
             for e in range(E)}
    if arrival == "env_reversed":
        drained = [(e, items[e]) for e in reversed(range(E))]
    elif arrival == "env_major":
        drained = list(items.items())
    else:      # each env's rows arrive as two batches, a window apart
        drained = [(e, [RecordBatch.from_columns(f"e{e}", "temp_c",
                                                 ts[:3], ts[:3] + e)])
                   for e in range(E)]
        drained += [(e, [RecordBatch.from_columns(f"e{e}", "temp_c",
                                                  ts[3:], ts[3:] + e)])
                    for e in range(E)]
    fleet = FleetAccumulator(STREAMS, M)
    stats = {e: _new_stats() for e in range(E)}
    fleet.ingest(drained, np.zeros(1), stats)
    out = _zeros(1, E, M)
    fleet.close_windows([(0.0, WIN)], out, stats)
    assert fleet.merge_stats[how] == 1 and fleet.sort_fallback == 0
    for e in range(E):
        assert out[0][0, e, 1, :6].tolist() == (ts + e).tolist()
        assert stats[e]["records"] == 6


@pytest.mark.parametrize("kind", ["batch", "multi_stream", "records"])
def test_fleet_call_of_unknown_streams_only(kind):
    """A call whose drained items all name unknown streams counts them per
    env, per window and stages nothing, as the per-env Accumulator
    does; the next call is unaffected."""
    ts = [10.0, 20.0, 150.0]
    if kind == "batch":
        items = [RecordBatch.from_columns("e", "zz", ts, ts)]
    elif kind == "multi_stream":
        items = [RecordBatch.from_records(
            [Record("e", nm, t, t) for nm, t in zip(["zz", "yy", "zz"], ts)])]
    else:
        items = [Record("e", "zz", t, t) for t in ts]
    bounds = [(0.0, 100.0), (100.0, 200.0)]
    starts = np.asarray([b[0] for b in bounds])
    fleet = FleetAccumulator(STREAMS, 4)
    stats = {0: _new_stats(), 1: _new_stats()}
    counts = fleet.ingest([(1, items)], starts, stats)
    out = _zeros(2, 2, 4)
    fleet.close_windows(bounds, out, stats)
    ref = Accumulator("e", STREAMS, 4)
    ref.ingest(items)
    assert counts.tolist() == _window_counts(items, starts).tolist() == [2, 1]
    assert stats[1] == ref.stats and stats[0] == _new_stats()
    assert not any(a.any() for a in out) and fleet.pending() == 0
    more = [RecordBatch.from_columns("e", "temp_c", [250.0], [1.0])]
    fleet.ingest([(1, more)], starts + 200.0, stats)
    fleet.close_windows([(a + 200.0, b + 200.0) for a, b in bounds], out,
                        stats)
    assert out[2][0, 1, 1, 0] and stats[1]["records"] == 1


def test_sorted_flag_propagates_receiver_translator_queue():
    # receiver: measured per poll (jitter can't exceed the interval here)
    dev = SimulatedDevice("s", interval_s=60.0, dropout_p=0.0, jitter_s=0.5,
                          spike_p=0.0)
    clock = {"now": 0.0}
    r = Receiver("src", "mqtt", dev, lambda: clock["now"])
    seen = []
    r.subscribe("e", on_batch=lambda e, s, ts, vs, srt: seen.append(srt))
    clock["now"] = 600.0
    r.poll_once()
    assert seen == [True]
    # translator passes the promise through; rename/scale never reorder
    tr = Translator("src", "mqtt", unit_scale=2.0)
    b = tr.translate_batch("e", "s", [1.0, 2.0], [3.0, 4.0], True)
    assert b.sorted_ts is True
    # queue overflow truncation keeps it (prefix of sorted is sorted)
    assert _head(b, 1).sorted_ts is True
    # default stays "unknown", never a false promise
    b2 = tr.translate_batch("e", "s", [2.0, 1.0], [3.0, 4.0])
    assert b2.sorted_ts is None


# --------------------------------------------------------------------------
# Fleet pass: one whole-fleet close == one Accumulator per env
# --------------------------------------------------------------------------

WIN = 100.0
T0 = 1e8        # float32 spacing is 8 s here: rebasing must subtract first


def _fleet_calls(seed, E, K, n_calls, ordered):
    """Per batch of K windows (``WIN`` long), each env's drained items.

    Rows fall from before the batch's first window start (stale, masked
    invalid) to two batches past its last end (carried across two
    closes), on a 5 s grid (timestamp ties, exact window bounds) plus
    0 or 0.125 s, at ``T0`` plus 100 s windows, with
    bursts past ``max_samples`` in one window, a stream no env knows
    (``zz``) and empty envs. Items mix ``Record`` singles, multi-stream
    batches and single-stream batches. ``ordered``: each stream's
    timestamps never decrease in arrival order, across batches too, and
    single-stream batches promise it (``sorted_ts=True``); otherwise rows
    arrive shuffled and batches say ``None`` or ``False``."""
    rng = np.random.RandomState(seed)
    names = STREAMS + ["zz"]
    last = np.full((E, len(names)), -np.inf)
    calls = []
    for c in range(n_calls):
        lo, hi = T0 + c * K * WIN - 150.0, T0 + (c + 3) * K * WIN
        per_env = []
        for e in range(E):
            if rng.rand() < 0.2:
                per_env.append([])
                continue
            rows = []
            for s, name in enumerate(names):
                n = rng.randint(0, 14 * K)
                ts = np.round(rng.uniform(max(lo, last[e, s]), hi, n)
                              / 5) * 5 + 0.125 * rng.randint(0, 2, n)
                if ordered:
                    ts = np.sort(np.maximum(ts, last[e, s]))
                    if n:
                        last[e, s] = ts[-1]
                rows += [Record(f"e{e}", name, float(t),
                                float(rng.normal(5, 2))) for t in ts]
            if ordered:
                # interleave the streams, each keeping its own order
                pick = rng.permutation(np.repeat(
                    np.arange(len(names)),
                    [sum(r.stream == nm for r in rows) for nm in names]))
                per = {nm: iter([r for r in rows if r.stream == nm])
                       for nm in names}
                rows = [next(per[names[i]]) for i in pick]
            else:
                rows = [rows[i] for i in rng.permutation(len(rows))]
            items, i = [], 0
            while i < len(rows):
                take = rows[i:i + 1 + rng.randint(10)]
                i += len(take)
                kind = rng.randint(3)
                if kind == 0:
                    items.extend(take)
                elif kind == 1:
                    items.append(RecordBatch.from_records(take))
                else:
                    for nm in names:
                        mine = [r for r in take if r.stream == nm]
                        if mine:
                            items.append(RecordBatch.from_columns(
                                f"e{e}", nm, [r.timestamp for r in mine],
                                [r.value for r in mine],
                                True if ordered else
                                [None, False][rng.randint(2)]))
            per_env.append(items)
        calls.append(per_env)
    return calls


def _zeros(K, E, M):
    shape = (K, E, len(STREAMS), M)
    return (np.zeros(shape, np.float32), np.zeros(shape, np.float32),
            np.zeros(shape, bool))


@pytest.mark.parametrize("ordered", [True, False])
@pytest.mark.parametrize("K", [1, 2, 8])
def test_fleet_pass_matches_per_env_accumulators(K, ordered):
    """Over four batches, the fleet pass and the per-env legacy
    Accumulators agree byte for byte on the staging buffers, the
    per-window counts and every env's stats; the carried records equal
    the per-env pending ones. Ordered input never needs
    the timestamp sort."""
    E, M = 6, 4
    calls = _fleet_calls(1000 + K, E, K, 4, ordered)
    fleet = FleetAccumulator(STREAMS, M)
    fleet_stats = {e: _new_stats() for e in range(E)}
    per = [Accumulator(f"e{e}", STREAMS, M) for e in range(E)]
    carried = fallbacks = 0
    for c, per_env in enumerate(calls):
        bounds = [(T0 + w * WIN, T0 + (w + 1) * WIN)
                  for w in range(c * K, (c + 1) * K)]
        starts = np.asarray([b[0] for b in bounds])
        got = _zeros(K, E, M)
        counts = fleet.ingest([(e, it) for e, it in enumerate(per_env) if it],
                              starts, fleet_stats)
        fleet.close_windows(bounds, got, fleet_stats)
        carried += fleet.pending()
        fallbacks += fleet.sort_fallback
        ref = _zeros(K, E, M)
        ref_counts = np.zeros(K, np.int64)
        for e, items in enumerate(per_env):
            ref_counts += _window_counts(items, starts)
            per[e].ingest(items)
            per[e].close_windows(bounds, rebase=True,
                                 out=tuple(a[:, e] for a in ref))
        for x, y in zip(got, ref):
            assert x.dtype == y.dtype and x.tobytes() == y.tobytes()
        assert (counts == ref_counts).all()
        assert [fleet_stats[e] for e in range(E)] == [a.stats for a in per]
        assert fleet.pending() == sum(a.pending() for a in per)
    stats = [fleet_stats[e] for e in range(E)]
    assert sum(s["overflow"] for s in stats) > 0
    assert sum(s["unknown_stream"] for s in stats) > 0
    assert carried > 0
    if ordered:
        assert fallbacks == 0
    else:
        assert fallbacks > 0


@pytest.mark.parametrize("ts,fallback", [([10.0, 50.0], 0),
                                         ([50.0, 10.0], 1)])
def test_fleet_sort_fallback_counter(ts, fallback):
    """``sort_fallback`` reads 1 exactly when a window's rows of one key
    arrived out of timestamp order, and the close still orders them."""
    fleet = FleetAccumulator(STREAMS, 4)
    stats = {0: _new_stats()}
    fleet.ingest([(0, [RecordBatch.from_columns("e", "temp_c", ts, ts)])],
                 np.zeros(1), stats)
    out = _zeros(1, 1, 4)
    fleet.close_windows([(0.0, WIN)], out, stats)
    assert fleet.sort_fallback == fallback
    assert out[0][0, 0, 1, :2].tolist() == [10.0, 50.0]
    assert stats[0]["records"] == 2 and fleet.pending() == 0


# --------------------------------------------------------------------------
# System level: every ingest configuration is bit-identical
# --------------------------------------------------------------------------

def _system(mode="scan", n_envs=2, scan_k=3, protocols=("mqtt", "amqp"),
            **kw):
    srcs = [
        SourceSpec("meter", protocols[0],
                   SimulatedDevice("grid_kw", 60.0, base=3.0, seed=1)),
        SourceSpec("price", protocols[1],
                   SimulatedDevice("price_eur", 300.0, base=0.2,
                                   amplitude=0.05, seed=2)),
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


@pytest.mark.parametrize("ingest", ["records", "columnar"])
@pytest.mark.parametrize("protocols", [("mqtt", "amqp"), ("http", "http")])
def test_fastpath_matches_legacy_per_ingest_path(ingest, protocols):
    """fastpath on == fastpath off for BOTH ingest paths and all codecs
    (including lossy http CSV: the wire rounding is identical on both
    sides of this comparison, so equality is exact)."""
    a = _system(ingest=ingest, protocols=protocols, ingest_fastpath=True)
    b = _system(ingest=ingest, protocols=protocols, ingest_fastpath=False)
    ra, rb = a.run_windows(7), b.run_windows(7)
    a.stop(), b.stop()
    assert _strip(ra) == _strip(rb)


@pytest.mark.parametrize("mode", ["scan", "scan_async", "scan_fused_decide",
                                  "fused"])
def test_fleet_pass_matches_legacy_per_mode(mode):
    """The fleet pass == the per-env legacy Accumulators through the scan,
    async (prefetcher epoch protocol), fused-decide and per-window
    engines; the replay sink sees identical rows."""
    ref = _system(mode=mode)
    got = _system(mode=mode, ingest_fastpath=False)
    assert ref.fleet is not None and got.fleet is None
    rr, rg = ref.run_windows(7), got.run_windows(7)
    assert _strip(rr) == _strip(rg)
    ra, rb = ref.export_replay("s"), got.export_replay("s")
    for k, v in ra.items():
        eq = (np.asarray(v) == np.asarray(rb[k]))
        assert eq if isinstance(eq, bool) else eq.all(), k
    ref.stop(), got.stop()


def test_fastpath_logdb_rows_identical(tmp_path):
    """The LogDB sink logs byte-identical rows under the fast path."""
    rows = {}
    for name, fast in (("fast", True), ("legacy", False)):
        db = LogDB(str(tmp_path / name), salt="x")
        s = _system(ingest_fastpath=fast, db=db)
        s.run_windows(6)
        s.stop(), db.close()
        rows[name] = [{k: v for k, v in r.items() if k != "logged_at"}
                      for _, r in db.read_from()]
    assert rows["fast"] == rows["legacy"] and len(rows["fast"]) == 12


def test_elastic_masked_pool_fastpath_identity():
    """Fast path under elastic churn (attach into a free slot, detach):
    identical per-window rows and replay export to the legacy path."""
    def run(fast):
        s = _system(n_envs=4, elastic=True, env_slots=4,
                    ingest_fastpath=fast)
        s.detach_env("bldg-3")                # start 3-of-4 occupied
        out = _strip(s.run_windows(3))
        s.attach_env("joiner")
        out += _strip(s.run_windows(3))
        s.detach_env("bldg-1")
        out += _strip(s.run_windows(3))
        exp = s.export_replay("s")
        s.stop()
        return out, exp
    (ra, ea), (rb, eb) = run(True), run(False)
    assert ra == rb
    for k, v in ea.items():
        eq = (np.asarray(v) == np.asarray(eb[k]))
        assert eq if isinstance(eq, bool) else eq.all(), k


def test_staging_buffers_not_reused_while_batch_alive():
    """The rotating staging pool must not overwrite a RawWindow that is
    still within the pipeline depth: the buffer an assembly returned is
    untouched for the next ``_STAGE_DEPTH - 1`` assemblies."""
    s = _system()
    k = s.scan_k
    def assemble():
        bounds = [s.window_bounds(s.window_index + j) for j in range(k)]
        s._advance_clock(bounds[-1][1])
        s.pump_receivers()
        raw, _ = s.assemble_windows(bounds)
        s.window_index += k
        return raw
    raw0 = assemble()
    snap = [np.array(np.asarray(x)) for x in
            (raw0.values, raw0.timestamps, raw0.valid)]
    for _ in range(PerceptaSystem._STAGE_DEPTH - 1):
        assemble()
    for a, b in zip(snap, (raw0.values, raw0.timestamps, raw0.valid)):
        assert (a == np.asarray(b)).all()
    s.stop()
