"""The trace reduction (busy union, program time, idle gaps labelled by
the host span) and the table of peaks."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import spec  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Plane  # noqa: E402

MS = 1_000_000


def _trace():
    host = Plane("/host:CPU", {"python": [
        ("bench.window", 0, 100 * MS),
        ("bench.deliver", 0, 30 * MS),
        ("bench.run_windows", 30 * MS, 100 * MS)]})
    dev = Plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 10 * MS, 20 * MS),
                    ("fusion.2", 15 * MS, 25 * MS),      # overlaps fusion.1
                    ("scatter", 60 * MS, 70 * MS),
                    ("late", 95 * MS, 120 * MS)],         # clipped at 100
        "XLA Modules": [("jit_run_many_decide(3)", 10 * MS, 25 * MS),
                        ("jit_train_step(7)", 60 * MS, 70 * MS),
                        ("jit_run_many_decide(3)", 95 * MS, 120 * MS)]})
    return [host, dev]


def test_busy_is_the_union_inside_the_window():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.100)
    # [10, 25] + [60, 70] + [95, 100] ms
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["n_devices"] == 1


def test_time_per_program_and_op():
    r = tr.reduce(_trace())
    secs, count = tr.program_seconds(r, "run_many_decide")
    assert secs == pytest.approx(0.020) and count == 2
    assert tr.program_seconds(r, "train_step") == (pytest.approx(0.010), 1)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.010)
    assert ops["late"] == pytest.approx(0.005)


def test_idle_gaps_are_labelled_by_the_host_span():
    r = tr.reduce(_trace())
    gaps = dict(r["idle_gaps"])
    # idle [0,10] under deliver; [25,60] mostly run_windows (30..60);
    # [70,95] under run_windows
    assert gaps["bench.deliver"] == pytest.approx(0.010)
    assert gaps["bench.run_windows"] == pytest.approx(0.060)
    assert sum(gaps.values()) == pytest.approx(0.070)


def test_busy_averages_over_devices_and_needs_one():
    planes = _trace()
    planes.append(Plane("/device:TPU:1", {"XLA Ops": [("x", 0, 50 * MS)]}))
    assert tr.reduce(planes)["busy_s"] == pytest.approx(0.040)
    with pytest.raises(ValueError):
        tr.reduce([planes[0]])


def test_union_merges_and_drops_empty():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]


def test_peaks_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


RECORDED = os.path.join(os.path.dirname(__file__), "data", "v5e_probe.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    """A trace recorded on one v5e chip: two small jitted programs run
    three times under host spans."""
    r = tr.reduce(tr.load(RECORDED))
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(v["count"] for v in r["programs"].values()) >= 6
    assert {g[0] for g in r["idle_gaps"]} <= {"bench.deliver",
                                              "bench.run_windows", "idle"}
