"""The program-span reduction (``span_reduce``) on hand-written events: the
span table, the idle gaps under ``bench.run_windows`` split by the
innermost program span, and the per-layer values read from the table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import span_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402
from span_reduce import Span  # noqa: E402
from trace_reduce import Plane  # noqa: E402

MS = 1_000_000
MGR, GEN = (0, 0), (0, 1)


def _planes():
    host = Plane("/host:CPU", {"python": [
        ("bench.window", 0, 100 * MS),
        ("bench.deliver", 0, 30 * MS),
        ("bench.run_windows", 30 * MS, 100 * MS)]})
    dev = Plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 10 * MS, 20 * MS),
                    ("fusion.2", 15 * MS, 25 * MS),
                    ("scatter", 60 * MS, 70 * MS),
                    ("late", 95 * MS, 120 * MS)],
        "XLA Modules": [("jit_run_many_decide(3)", 10 * MS, 25 * MS),
                        ("jit_train_step(7)", 60 * MS, 70 * MS)]})
    return [host, dev]


def _sp(name, s, e, thread=MGR, **meta):
    return Span(name, s * MS, e * MS, thread, meta)


def _spans():
    # idle: [0,10] under deliver; [25,60] and [70,95] under run_windows
    return [
        _sp("bench.window", 0, 100),
        _sp("bench.deliver", 0, 30, GEN),
        _sp("bench.run_windows", 30, 100),
        _sp("percepta.run_windows", 30, 100, n=8),
        _sp("percepta.batch", 31, 99, k=8, window=0),
        _sp("percepta.assemble", 31, 50, records=100, close_ms=4.0,
            queue_wait_ms=2.0),
        _sp("percepta.dispatch", 50, 65),
        _sp("percepta.fused_step", 52, 58),
        _sp("percepta.consume", 65, 99),
        _sp("percepta.result_wait", 65, 80),
        _sp("percepta.forward", 80, 97),
        # another thread's span never names the manager's idle time
        _sp("percepta.pump", 70, 95, GEN),
        # outside bench.window: left out
        _sp("percepta.assemble", 120, 130, records=1000),
    ]


def test_span_table_counts_self_time_and_metadata():
    t = sr.reduce(_planes(), _spans())["spans"]
    assert t["percepta.assemble"]["count"] == 1
    assert t["percepta.assemble"]["seconds"] == pytest.approx(0.019)
    assert t["percepta.assemble"]["sums"] == {
        "records": 100, "close_ms": 4.0, "queue_wait_ms": 2.0}
    assert t["percepta.assemble"]["events"] == [
        {"records": 100, "close_ms": 4.0, "queue_wait_ms": 2.0}]
    assert t["percepta.pump"]["count"] == 1
    # self: the span's time that no child on its thread covers
    assert t["percepta.run_windows"]["self_seconds"] == pytest.approx(0.002)
    assert t["percepta.batch"]["self_seconds"] == pytest.approx(0.0)
    assert t["percepta.dispatch"]["self_seconds"] == pytest.approx(0.009)
    assert t["percepta.consume"]["self_seconds"] == pytest.approx(0.002)
    assert t["percepta.forward"]["self_seconds"] == pytest.approx(0.017)
    assert t["percepta.batch"]["sums"] == {"k": 8, "window": 0}
    assert not t["percepta.consume"]["leaf"] and t["percepta.forward"]["leaf"]
    assert "bench.window" not in t


def test_idle_under_run_windows_split_by_innermost_program_span():
    r = sr.reduce(_planes(), _spans())
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({
        "bench.deliver": 0.010,
        "bench.run_windows": 0.005,       # [25,30]: no program span open
        "percepta.run_windows": 0.001,
        "percepta.assemble": 0.019,
        "percepta.dispatch": 0.004,
        "percepta.fused_step": 0.006,
        "percepta.result_wait": 0.010,
        "percepta.forward": 0.015})
    assert sum(gaps.values()) == pytest.approx(
        sum(v for _, v in tr.reduce(_planes())["idle_gaps"]))
    c = r["cover"]
    assert c["manager_idle_s"] == pytest.approx(0.060)
    assert c["named_share"] == pytest.approx(55 / 60)
    assert c["leaf_share"] == pytest.approx(50 / 60)
    assert c["steps_share"] == pytest.approx(68 / 70)


def test_without_program_spans_the_gaps_are_trace_reduce_s():
    bench_only = [x for x in _spans() if x.name.startswith("bench.")]
    r = sr.reduce(_planes(), bench_only)
    assert r["spans"] == {}
    assert sorted(r["idle_gaps"]) == sorted(tr.reduce(_planes())["idle_gaps"])
    assert r["cover"]["named_share"] == 0


def test_innermost_pieces_of_nested_and_sibling_spans():
    pieces, parents = sr.innermost([
        _sp("a", 0, 10), _sp("b", 2, 4), _sp("c", 4, 6), _sp("d", 12, 14)])
    assert pieces == [(0, 2 * MS, "a"), (2 * MS, 4 * MS, "b"),
                      (4 * MS, 6 * MS, "c"), (6 * MS, 10 * MS, "a"),
                      (12 * MS, 14 * MS, "d")]
    assert parents == {"a"}


PER_LAYER = {
    "assemble_host_ms.backlog": 19 / 8,
    "close_windows_host_ms.backlog": 4.0 / 8,
    "dispatch_host_ms.backlog": 15 / 8,
    "result_wait_ms.backlog": 15 / 8,
    "forward_host_ms.backlog": 17 / 8,
    "queue_wait_ms.open": 2.0,
    "assemble_host_ms.open": 19.0,
    "dispatch_host_ms.open": 15.0,
}


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_per_layer_value(name):
    table = sr.reduce(_planes(), _spans())["spans"]
    assert sr.PER_LAYER[name](table) == pytest.approx(PER_LAYER[name])


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_per_layer_value_is_none_without_program_spans(name):
    bench_only = [x for x in _spans() if x.name.startswith("bench.")]
    assert sr.PER_LAYER[name](sr.reduce(_planes(), bench_only)["spans"]) \
        is None
