"""Elastic env membership (``elastic=True``): masked slot pools.

The env axis becomes a padded slot pool: ``env_slots`` rows, an
``active`` mask riding the carry, attach/detach at batch boundaries with
NO retrace, and :meth:`PerceptaSystem.resize` pool regrowth (the one
allowed retrace). The testable contract, in order of strength:

* a live env's rows are BIT-IDENTICAL to a dense fixed-E system over the
  same envs — not close, identical (the mask combines by fenced select
  only; ``core.pipeline.mask_env_rows`` documents why the fences matter);
* membership churn (detach, reattach into a recycled slot, regrow) never
  perturbs the rows of envs that stayed attached;
* regrowth across a real mesh-split boundary (4 -> 8 slots on 8 forced
  host devices) resumes surviving rows bit-exactly.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.records import RecordBatch
from repro.runtime.system import PerceptaSystem, SourceSpec

# every engine the elastic refactor touches that runs in-process (the
# sharded modes degenerate to a 1-device mesh here; the real 8-device
# mesh is the subprocess test at the bottom)
ELASTIC_MODES = ("scan", "scan_sharded", "scan_async", "scan_fused_decide",
                 "scan_fused_decide_sharded", "scan_fused_decide_async")

STABLE = ["s0", "s1", "s2"]      # attached at construction, never touched


def _mk(env_ids, slots=None, elastic=False, mode="scan", scan_k=3, cap=16,
        **kw):
    # off-tick reading intervals (9.7 / 31.3 s): no reading ever lands
    # exactly on a window boundary, so window membership can't flip on a
    # float comparison between runs
    srcs = [SourceSpec("grid_kw", "mqtt",
                       SimulatedDevice("grid", 9.7, base=3.0, seed=1)),
            SourceSpec("price_eur", "http",
                       SimulatedDevice("price", 31.3, base=0.2, seed=2))]
    n = slots if slots is not None else len(env_ids)
    cfg = PipelineConfig(n_envs=n, n_streams=2, n_ticks=8, tick_s=60.0,
                         max_samples=32)
    pred = Predictor(linear_policy(cfg.n_features, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     n, cfg.n_features, replay_capacity=cap)
    return PerceptaSystem(list(env_ids), srcs, cfg, pred, speedup=5000.0,
                          manual_time=True, mode=mode, scan_k=scan_k,
                          env_slots=slots, elastic=elastic, **kw)


def _strip(results):
    return [{k: v for k, v in r.items() if k != "latency_s"}
            for r in results]


def _assert_rows_equal(dense_export, elastic_export):
    """Every env of the dense export has bit-identical replay rows in the
    elastic one. Exports pseudonymize env ids, but both sides use the same
    salt, so a shared env carries the same exported id — rows join on it
    (the elastic extra rows are churned tenants and free-slot
    placeholders, not part of the dense reference)."""
    ea = {e: i for i, e in enumerate(elastic_export["env_ids"])}
    for i, env in enumerate(dense_export["env_ids"]):
        assert env in ea, env
        j = ea[env]
        for k in ("obs", "actions", "rewards", "next_obs", "tick_idx",
                  "times", "valid"):
            a = np.asarray(dense_export[k])[i]
            b = np.asarray(elastic_export[k])[j]
            assert (a == b).all(), (env, k)


# --------------------------------------------------------------------------
# Static subset: live rows of a part-full pool == a dense fixed-E system
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ELASTIC_MODES)
def test_elastic_static_subset_matches_dense(mode):
    """3 live envs in a 4-slot pool vs a dense E=3 ``scan`` reference: the
    per-window results AND the banked replay rows are bit-identical (16
    windows over scan_k=3 — full batches + a ragged tail)."""
    dense = _mk(STABLE)
    el = _mk(STABLE, slots=4, elastic=True, mode=mode)
    rd, re_ = dense.run_windows(16), el.run_windows(16)
    assert _strip(rd) == _strip(re_)
    ed, ee = dense.export_replay("s"), el.export_replay("s")
    # elastic exports at the full pool width: the 3 live rows join the
    # dense rows by exported id, the free 4th slot never banked anything
    assert ee["env_ids"][:3] == ed["env_ids"] and len(ee["env_ids"]) == 4
    _assert_rows_equal(ed, ee)
    assert not np.asarray(ee["valid"])[3].any()
    dense.stop(), el.stop()


# --------------------------------------------------------------------------
# Membership plumbing: guards, slot recycling, fresh rows on reattach
# --------------------------------------------------------------------------

def test_membership_guards():
    el = _mk(STABLE, slots=4, elastic=True, mode="scan_fused_decide")
    with pytest.raises(AssertionError, match="already attached"):
        el.attach_env("s0")
    with pytest.raises(AssertionError, match="not attached"):
        el.detach_env("ghost")
    el.stop()
    dense = _mk(STABLE)
    with pytest.raises(AssertionError, match="elastic=True"):
        dense.attach_env("s3")
    dense.stop()
    with pytest.raises(ValueError, match="scan engine"):
        _mk(STABLE, slots=4, elastic=True, mode="fused")


def test_detach_reattach_recycles_slot_with_fresh_rows():
    """Detach then reattach the same env: it returns to the SAME slot, its
    old transitions are scrubbed (a later tenant never sees them), and the
    reattached env re-banks from a fresh prev chain — exactly
    ``scan_k - 1`` transitions after one post-reattach batch."""
    el = _mk(STABLE, slots=4, elastic=True, mode="scan_fused_decide",
             scan_k=3, cap=64)
    el.run_windows(6)
    freed = el.detach_env("s1")
    assert el.env_ids == ["s0", "s2"]
    el.run_windows(3)
    got = el.attach_env("s1")
    assert got == freed                   # lowest free slot is recycled
    assert el.env_ids == STABLE
    el.run_windows(3)
    valid = np.asarray(el.export_replay("s")["valid"])
    # slots are positional: s0/s1/s2 took slots 0/1/2 at construction and
    # s1 came back to its recycled slot 1. s1 was scrubbed on detach, then
    # one 3-window batch with no predecessor for window 0 -> 2 banked
    # rows; s0/s2 banked through all 12 windows
    assert valid[1].sum() == 2
    assert valid[0].sum() == 11 and valid[2].sum() == 11
    el.stop()


def test_attach_env_grows_full_pool():
    """Attaching into a full pool regrows it (4 -> 8 slots) and the new
    env lands in the first slot of the padding."""
    el = _mk(STABLE + ["c0"], slots=4, elastic=True, mode="scan_fused_decide")
    el.run_windows(3)
    assert el.env_slots == 4 and not el._free_slots
    slot = el.attach_env("c1")
    assert el.env_slots == 8 and slot == 4
    res = el.run_windows(3)
    assert all(np.isfinite(r["mean_reward"]) for r in res)
    el.stop()


# --------------------------------------------------------------------------
# Carried records: readings delivered ahead of the batch that takes them
# --------------------------------------------------------------------------

def _deliver_ahead(system, env_ids, w0, n_windows, seed):
    """Publish 12 readings a window per env and stream for windows
    [w0, w0 + n_windows) straight to the queues, as one sorted batch each
    (no receiver poll), so a batch ending before them carries the rest."""
    rng = np.random.RandomState(seed)
    t0 = system.window_bounds(w0)[0]
    for env in env_ids:
        for src in system.sources:
            n = 12 * n_windows
            ts = np.sort(rng.uniform(t0, t0 + n_windows * system.window_s,
                                     n))
            system.broker.publish(RecordBatch.from_columns(
                env, src.device.stream, ts, rng.normal(5.0, 1.0, n),
                sorted_ts=True))


def test_detach_discards_carried_records_as_reset_does():
    """Detaching an env with carried records discards exactly what the
    per-env legacy Accumulator's ``reset`` returns; the env re-attaches to
    an empty slot, and every later batch matches the legacy system."""
    fast = _mk(STABLE, slots=4, elastic=True)
    slow = _mk(STABLE, slots=4, elastic=True, ingest_fastpath=False)
    rows = {}
    for s in (fast, slow):
        _deliver_ahead(s, STABLE, 0, 9, seed=0)
        rows[s] = _strip(s.run_windows(3, pump=False))
    slot = fast._slot_env.index("s1")
    before = fast.fleet.pending()
    assert before > 0
    fast.detach_env("s1")
    n_slow = slow.accumulators["s1"].reset()
    slow.detach_env("s1")
    assert before - fast.fleet.pending() == n_slow > 0
    for s in (fast, slow):
        assert s.attach_env("s1") == slot
    assert fast.fleet.discard(slot) == 0
    for s in (fast, slow):
        _deliver_ahead(s, ["s1"], 3, 6, seed=1)
        rows[s] += _strip(s.run_windows(6, pump=False))
    assert rows[fast] == rows[slow]
    ea, eb = fast.export_replay("s"), slow.export_replay("s")
    for k, v in ea.items():
        eq = (np.asarray(v) == np.asarray(eb[k]))
        assert eq if isinstance(eq, bool) else eq.all(), k
    fast.stop(), slow.stop()


@pytest.mark.parametrize("mode", ("scan", "scan_fused_decide"))
def test_resize_keeps_carried_records(mode):
    """A pool regrown (4 -> 8 slots) while records are carried: the live
    envs' results stay bit-identical to a dense fixed-E system."""
    dense = _mk(STABLE, mode=mode)
    el = _mk(STABLE, slots=4, elastic=True, mode=mode)
    out = {}
    for s in (dense, el):
        _deliver_ahead(s, STABLE, 0, 9, seed=2)
        out[s] = _strip(s.run_windows(3, pump=False))
    assert el.fleet.pending() > 0
    el.resize()
    assert el.env_slots == 8
    for s in (dense, el):
        _deliver_ahead(s, STABLE, 9, 3, seed=3)
        out[s] += _strip(s.run_windows(9, pump=False))
    assert out[dense] == out[el]
    assert el.fleet.pending() == 0
    _assert_rows_equal(dense.export_replay("s"), el.export_replay("s"))
    dense.stop(), el.stop()


# --------------------------------------------------------------------------
# Property: random churn schedules never perturb the stable envs' rows
# --------------------------------------------------------------------------

# per-boundary ops; invalid draws degrade to no-ops so every schedule runs
OP_NONE, OP_ATTACH, OP_DETACH, OP_RECYCLE, OP_RESIZE = range(5)


def _run_schedule(ops, mode):
    """Apply attach/detach/reattach-into-recycled-slot/regrow ops between
    K=6 window batches, then assert the stable envs' replay rows are
    bit-identical to a dense fixed-E system that never churned. Replay
    capacity 4 against K=6 exercises ring wraparound under a partial mask
    on every batch (5 banked rows > 4 slots)."""
    K = 6
    el = _mk(STABLE, slots=4, elastic=True, mode=mode, scan_k=K, cap=4)
    churn, next_c = [], 0
    total = K                              # leading batch before any churn
    el.run_windows(K)
    for op in ops:
        if op == OP_ATTACH and next_c < 4:
            churn.append(f"c{next_c}")
            el.attach_env(churn[-1])       # regrows by itself when full
            next_c += 1
        elif op == OP_DETACH and churn:
            el.detach_env(churn.pop(0))
        elif op == OP_RECYCLE and churn:
            freed = el.detach_env(churn[0])
            assert el.attach_env(churn[0]) == freed
        elif op == OP_RESIZE and el.env_slots < 16:
            el.resize()
        el.run_windows(K)
        total += K
    dense = _mk(STABLE, scan_k=K, cap=4)
    dense.run_windows(total)
    _assert_rows_equal(dense.export_replay("s"), el.export_replay("s"))
    dense.stop(), el.stop()


@pytest.mark.parametrize("ops", [
    (OP_ATTACH, OP_RECYCLE, OP_DETACH),    # fill, recycle a slot, free it
    (OP_ATTACH, OP_ATTACH, OP_ATTACH),     # 3rd attach fills -> auto-regrow
    (OP_RESIZE, OP_ATTACH, OP_RECYCLE),    # explicit regrow, churn after
])
@pytest.mark.parametrize("mode", ("scan", "scan_fused_decide"))
def test_elastic_churn_schedules_match_dense(ops, mode):
    """Deterministic anchor schedules for :func:`_run_schedule` — always
    run, even where hypothesis is unavailable."""
    _run_schedule(ops, mode)


# property test: random schedules. repro.testing hands out real hypothesis
# when installed and a deterministic drop-in otherwise, so this runs (never
# skips) in every environment.
from repro.testing import given, settings, st  # noqa: E402


@given(ops=st.lists(st.integers(OP_NONE, OP_RESIZE),
                    min_size=2, max_size=3),
       mode=st.sampled_from(("scan", "scan_fused_decide")))
@settings(max_examples=8, deadline=None)
def test_elastic_random_schedule_matches_dense(ops, mode):
    """Random schedules over the same op alphabet as the anchors."""
    _run_schedule(tuple(ops), mode)


# --------------------------------------------------------------------------
# Real 8-device mesh: pool growth crosses a mesh-split boundary
# --------------------------------------------------------------------------

_MESH_GROW_SCRIPT = """
import numpy as np
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.records import RecordBatch
from repro.runtime.system import PerceptaSystem, SourceSpec
import jax
assert len(jax.devices()) == 8, jax.devices()

def mk(env_ids, slots=None, elastic=False, mode="scan"):
    srcs = [SourceSpec("grid_kw", "mqtt",
                       SimulatedDevice("grid", 9.7, base=3.0, seed=1)),
            SourceSpec("price_eur", "http",
                       SimulatedDevice("price", 31.3, base=0.2, seed=2))]
    n = slots if slots is not None else len(env_ids)
    cfg = PipelineConfig(n_envs=n, n_streams=2, n_ticks=8, tick_s=60.0,
                         max_samples=32)
    pred = Predictor(linear_policy(cfg.n_features, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     n, cfg.n_features, replay_capacity=64)
    return PerceptaSystem(list(env_ids), srcs, cfg, pred, speedup=5000.0,
                          manual_time=True, mode=mode, scan_k=3,
                          env_slots=slots, elastic=elastic)

stable = ["s0", "s1", "s2"]
el = mk(stable, slots=4, elastic=True, mode="scan_fused_decide_sharded")
assert dict(el.pipeline.mesh.shape) == {"data": 4}, el.pipeline.mesh
el.run_windows(6)
el.resize()                                # 4 -> 8: mesh splits 4 -> 8 ways
assert el.env_slots == 8
assert dict(el.pipeline.mesh.shape) == {"data": 8}, el.pipeline.mesh
el.attach_env("c0")                        # new tenant in the padding
el.run_windows(6)

dense = mk(stable)
dense.run_windows(12)
ed, ee = dense.export_replay("s"), el.export_replay("s")
ea = {e: i for i, e in enumerate(ee["env_ids"])}
for i, env in enumerate(ed["env_ids"]):      # exported ids join the rows
    for k in ("obs", "actions", "rewards", "next_obs", "tick_idx", "times",
              "valid"):
        a = np.asarray(ed[k])[i]
        b = np.asarray(ee[k])[ea[env]]
        assert (a == b).all(), (env, k)
dense.stop(), el.stop()
print("ELASTIC_MESH_GROW_OK")
"""


def test_elastic_regrow_across_mesh_split_boundary():
    """Forced 8-host-device CPU mesh: an elastic fused-sharded system
    regrows 4 -> 8 slots — the env mesh re-splits from 4 to 8 devices —
    and the three surviving envs' rows stay bit-identical to a dense,
    never-resized, single-device reference."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _MESH_GROW_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ELASTIC_MESH_GROW_OK" in out.stdout
