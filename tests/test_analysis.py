"""Static analysis layer (PR 6): jaxpr contract checker + AST invariant lint.

The jaxpr half must reject exactly the divergences that bit us in real PRs
— the K-leading/env-rows gemm and cross-env reductions from the PR 5
sharded fused engine, the float32 absolute-time cast from the PR 3
long-horizon collapse — while accepting every builtin policy/reward/decide
path, with diagnostics that name the offending primitive and source line.
The AST half gets a bad/good fixture pair per rule, plus the pragma,
baseline and repo-clean pins that make it a CI gate.
"""
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.analysis import (
    ContractViolation, JAXPR_RULES, LINT_RULES, Rules,
    check_builtins, check_decide_fns, check_fn, check_policy,
    check_reward_fn, check_reward_terms, check_system, check_train_step,
)
from repro.analysis import lint as lint_mod
from repro.core.reward import RewardSpec, RewardTerm, energy_reward_spec
from repro.distribution import sharding
from repro.runtime.predictor import (ActionSpace, ModelAdapter, Predictor,
                                     linear_policy)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
E, F, A = 4, 6, 2


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# jaxpr checker: the shard-invariance (env) family
# ---------------------------------------------------------------------------

def test_gemm_policy_rejected_with_primitive_and_source():
    """The exact PR 5 divergence shape: an (E,F)@(F,A) policy gemm puts the
    env axis into dot_general rows (row-count-dependent CPU kernels)."""
    W = jnp.ones((F, A))
    with pytest.raises(ContractViolation) as ei:
        check_policy(ModelAdapter(lambda f: f @ W, name="gemm"), F, n_envs=E)
    msg = str(ei.value)
    assert "env-gemm-rows" in msg
    assert "dot_general" in msg              # names the primitive
    assert "test_analysis.py" in msg         # names the source line
    assert "linear_policy" in msg            # actionable: points at the fix


def test_env_contraction_rejected():
    """Contracting OVER the env axis (worse than rows: mixes envs)."""
    v, _ = check_fn(lambda f, w: jnp.einsum("ef,e->f", f, w),
                    (_sds((E, F)), _sds((E,))), ("env:0", "env:0"))
    assert [x.rule for x in v] == ["env-contraction"]


def test_cross_env_mean_reward_rejected():
    """A custom reward normalizing by the batch mean — fine per-window on
    the host, garbage per shard under the env mesh."""
    bad = lambda f, a, p: f[:, 0] - jnp.mean(f[:, 0], axis=0)
    with pytest.raises(ContractViolation) as ei:
        check_reward_fn(bad, E, F, A)
    assert "env-reduce" in str(ei.value)
    assert "reduce" in str(ei.value)         # primitive named


def test_env_axis_tracked_through_transforms():
    """Provenance survives transpose/reshape/broadcast before the reduce."""
    def fn(f):
        g = jnp.transpose(f)                 # (F, E): env now axis 1
        g = g.reshape(F, 1, E)               # env now axis 2
        return g.sum(axis=2)                 # reduces the env axis
    v, _ = check_fn(fn, (_sds((E, F)),), ("env:0",))
    assert [x.rule for x in v] == ["env-reduce"]


def test_feature_reduce_is_clean():
    """Reducing over F (linear_policy's multiply+reduce dot) is the
    sanctioned phrasing — env rows stay independent."""
    def fn(f, w):
        return jnp.sum(f[:, None, :] * w.T[None, :, :], axis=-1)
    v, _ = check_fn(fn, (_sds((E, F)), _sds((F, A))), ("env:0", ""))
    assert v == []


def test_env_rules_scoped_to_sharded():
    """Rules(env=False) (the non-sharded fused engine) accepts a gemm —
    examples/serve_edge.py's LM policy is legal there."""
    W = jnp.ones((F, A))
    check_policy(ModelAdapter(lambda f: f @ W, name="gemm"), F, n_envs=E,
                 rules=Rules(env=False))


# ---------------------------------------------------------------------------
# jaxpr checker: time, collectives, callbacks, reward shape
# ---------------------------------------------------------------------------

def test_float32_cast_of_absolute_time_rejected():
    """The PR 3 collapse shape: int32 tick * 60.0 promotes the absolute
    tick counter to float32 seconds (quantizes past t~2^24)."""
    v, _ = check_fn(lambda t: t * 60.0, (_sds((), jnp.int32),), ("time",))
    assert [x.rule for x in v] == ["time-cast"]
    assert "2^24" in v[0].message


def test_relative_time_cast_is_clean():
    """Rebase-to-relative then narrow — the documented fix — passes: the
    abs-time tag clears on sub(time, time)."""
    def fn(t, t0):
        return (t - t0).astype(jnp.float32) * 60.0
    v, _ = check_fn(fn, (_sds((), jnp.int32), _sds((), jnp.int32)),
                    ("time", "time"))
    assert v == []


def test_time_phase_mod_is_clean():
    """t mod period (seasonal slot math) clears the tag too."""
    v, _ = check_fn(lambda t: (t % 24).astype(jnp.float32),
                    (_sds((), jnp.int32),), ("time",))
    assert v == []


def test_integer_tick_arithmetic_is_clean():
    v, _ = check_fn(lambda t: t + 1, (_sds((), jnp.int32),), ("time",))
    assert v == []


def test_callback_in_scan_rejected_and_scoped():
    noisy = lambda x: (jax.debug.print("x={x}", x=x), x * 2.0)[1]
    # checked entry points are scan-body-bound by default
    v, _ = check_fn(noisy, (_sds((E,)),), ("",))
    assert [x.rule for x in v] == ["callback-in-scan"]
    # a genuinely top-level fn is fine...
    v, _ = check_fn(noisy, (_sds((E,)),), ("",), scan_bound=False)
    assert v == []
    # ...until the callback sits inside its lax.scan body
    def scanned(x):
        return jax.lax.scan(lambda c, xi: (c + noisy(xi), None), 0.0, x)[0]
    v, _ = check_fn(scanned, (_sds((E,)),), ("",), scan_bound=False)
    assert [x.rule for x in v] == ["callback-in-scan"]


def test_collective_rejected_through_shard_map():
    """The checker recurses into the shard_map eqn the compat shim emits."""
    mesh = sharding.env_mesh(E)
    def fn(x):
        body = lambda xs: jax.lax.psum(xs, sharding.ENV_AXIS)
        from jax.sharding import PartitionSpec as P
        return compat.shard_map(body, mesh=mesh,
                                in_specs=P(sharding.ENV_AXIS),
                                out_specs=P())(x)
    v, _ = check_fn(fn, (_sds((E,)),), ("env:0",))
    assert "collective" in [x.rule for x in v]


def test_compat_pallas_seams():
    """The Pallas internals the checker reads, through ``repro.compat`` on
    the installed JAX: a pallas_call's grid mapping, its per-dim block
    sizes (``Blocked`` and ``Squeezed`` entries) and index-map
    evaluation."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[0] * 2.0

    def fn(x):
        return pl.pallas_call(
            kernel, grid=(4,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((None, 128), lambda i: (3 - i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
            interpret=True)(x)

    eqn, = [e for e in jax.make_jaxpr(fn)(jnp.ones((4, 128))).jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    gm = compat.pallas_grid_mapping(eqn)
    assert tuple(gm.grid) == (4,)
    assert (gm.num_inputs, gm.num_outputs) == (1, 1)
    bm_in, bm_out = gm.block_mappings
    assert compat.pallas_block_sizes(bm_in) == (1, 128)
    assert compat.pallas_block_sizes(bm_out) == (1, 128)
    routes = [tuple(int(r) for r in
                    compat.eval_jaxpr(bm_out.index_map_jaxpr, np.int32(i)))
              for i in range(4)]
    assert routes == [(3, 0), (2, 0), (1, 0), (0, 0)]
    assert "test_analysis.py" in compat.source_summary(eqn)


def test_compile_cache_location(monkeypatch):
    """Entry points keep JAX's compile cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        compat.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None  # JAX's choice
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        compat.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_reward_shape_rule():
    with pytest.raises(ContractViolation) as ei:
        check_reward_fn(lambda f, a, p: f[:1, 0], E, F, A)
    assert "reward-shape" in str(ei.value)
    assert "(E,)" in str(ei.value)


# ---------------------------------------------------------------------------
# jaxpr checker: every builtin passes
# ---------------------------------------------------------------------------

def test_all_builtins_accepted():
    """linear_policy, every RewardTerm kind (through RewardSpec.compute),
    energy_reward_spec, validate_actions, the builtin DecideFns pair (plus
    its elastic masked variant under the env-mask-gate family), and the
    four registry policies (certified against the full catalog)."""
    assert check_builtins() == 17


def test_real_predictor_decide_fns_accepted():
    pred = Predictor(linear_policy(F, A),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.full(A, -1.0), np.full(A, 1.0)),
                     E, F, replay_capacity=8)
    check_decide_fns(pred.make_decide_fn(), pred.decide_state(), E, F)


def test_decide_fns_with_bad_custom_reward_rejected():
    spec = RewardSpec((RewardTerm("custom",
                                  fn=lambda f, a, p: f[:, 0] - f[:, 0].max()),),
                      unchecked=True)      # sneak past spec-time check
    pred = Predictor(linear_policy(F, A), spec,
                     ActionSpace(np.full(A, -1.0), np.full(A, 1.0)),
                     E, F, replay_capacity=8)
    with pytest.raises(ContractViolation) as ei:
        check_decide_fns(pred.make_decide_fn(), pred.decide_state(), E, F)
    assert "env-reduce" in str(ei.value)


# ---------------------------------------------------------------------------
# jaxpr checker: the env-mask-gate family (elastic slot pools)
# ---------------------------------------------------------------------------

def test_mask_compaction_cumsum_rejected():
    """The bad fixture: cumsum of the active mask along the env axis is
    the row-compaction-offset pattern — row placement then depends on
    membership, which breaks the no-retrace bit-exactness contract."""
    def bad(feats, active):
        off = jnp.cumsum(active.astype(jnp.int32))
        return jnp.take(feats, off - 1, axis=0)

    v, _ = check_fn(bad, (_sds((E, F)), _sds((E,), jnp.bool_)),
                    ("env:0", "env:0,mask"),
                    rules=Rules(env=False, mask=True))
    rules_hit = {x.rule for x in v}
    assert "env-mask-gate" in rules_hit
    prims = {x.primitive for x in v if x.rule == "env-mask-gate"}
    assert "cumsum" in prims          # the offset scan itself
    assert "gather" in prims          # and the mask-derived indexing


def test_mask_sort_and_dynamic_slice_rejected():
    def bad_sort(feats, active):
        order = jnp.argsort(active.astype(jnp.int32))
        return feats, order

    v, _ = check_fn(bad_sort, (_sds((E, F)), _sds((E,), jnp.bool_)),
                    ("env:0", "env:0,mask"),
                    rules=Rules(env=False, mask=True))
    assert "env-mask-gate" in {x.rule for x in v}

    def bad_slice(feats, active):
        start = jnp.sum(active.astype(jnp.int32))
        return jax.lax.dynamic_slice(feats, (start, 0), (1, F))

    v, _ = check_fn(bad_slice, (_sds((E, F)), _sds((E,), jnp.bool_)),
                    ("env:0", "env:0,mask"),
                    rules=Rules(env=False, mask=True))
    assert "env-mask-gate" in {x.rule for x in v}


def test_mask_select_gating_accepted():
    """The sanctioned combinators: where/select and multiply keep row i's
    output a function of row i's mask bit alone — and the select predicate
    does NOT leak the mask tag into the selected values."""
    def good(feats, active):
        gated = jnp.where(active[:, None], feats, 0.0)
        return gated * active[:, None].astype(jnp.float32)

    v, _ = check_fn(good, (_sds((E, F)), _sds((E,), jnp.bool_)),
                    ("env:0", "env:0,mask"),
                    rules=Rules(env=False, mask=True))
    assert v == []


def test_elastic_decide_fns_accepted_and_gated():
    """The SHIPPED masked decide path passes the gate; a step that
    compacts rows with the carried mask is rejected through the same
    entry point (check_decide_fns auto-enables the family when the state
    carries an ``active`` leaf)."""
    pred = Predictor(linear_policy(F, A),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.full(A, -1.0), np.full(A, 1.0)),
                     E, F, replay_capacity=8)
    el_state = pred.decide_state()._replace(
        active=jnp.arange(E) < 2, prev_ok=jnp.zeros((E,), bool))
    decide = pred.make_decide_fn()
    check_decide_fns(decide, el_state, E, F)   # shipped path: clean

    def compacting_step(carry, feats):
        off = jnp.cumsum(carry.active.astype(jnp.int32))
        packed = jnp.take(feats.features, off - 1, axis=0)
        return decide.step(carry, feats._replace(features=packed))

    bad = decide._replace(step=compacting_step)
    with pytest.raises(ContractViolation) as ei:
        check_decide_fns(bad, el_state, E, F)
    assert "env-mask-gate" in str(ei.value)


# ---------------------------------------------------------------------------
# jaxpr checker: the online train step (OnlineTrainer's construction gate)
# ---------------------------------------------------------------------------

def _train_fixture():
    from repro.core import replay as rp
    buf = rp.init(E, 8, F, A)
    params = {"w": jnp.zeros((F, A), jnp.float32)}
    tstate = {"m": {"w": jnp.zeros((F, A), jnp.float32)},
              "step": jnp.zeros((), jnp.int32)}
    return rp, buf, params, tstate


def test_train_step_raw_tick_weighting_rejected():
    """The bad fixture: a loss that weights transitions by the RAW tick
    index casts absolute time to float32 — the t~2^24 collapse class, now
    inside the update. The replay ``tick_idx`` column enters tagged, and
    the tag must survive the minibatch gather."""
    rp_mod, buf, params, tstate = _train_fixture()

    def bad(params, tstate, replay, rng):
        batch = rp_mod.sample_device(replay, rng, 8)
        w = batch["tick_idx"].astype(jnp.float32)     # absolute-time cast
        return jnp.sum(w * batch["rewards"]) + jnp.sum(params["w"])

    with pytest.raises(ContractViolation) as ei:
        check_train_step(bad, params, tstate, buf)
    assert "time-cast" in str(ei.value)


def test_train_step_rebased_tick_weighting_and_batch_reduce_accepted():
    """The good twin: rebase tick_idx to a relative age FIRST (subtracting
    two absolute times clears the tag), then narrow — and reduce freely
    over the sampled batch axis (a minibatch mean is the point; the env
    family is off for the train step)."""
    rp_mod, buf, params, tstate = _train_fixture()

    def good(params, tstate, replay, rng):
        batch = rp_mod.sample_device(replay, rng, 8)
        age = (batch["tick_idx"] - batch["tick_idx"][0]).astype(jnp.float32)
        w = jnp.exp(-jnp.abs(age) / 100.0) * batch["valid"]
        err = jnp.sum(jnp.square(batch["actions"]), axis=-1)
        return jnp.mean(w * err) + jnp.sum(params["w"])

    check_train_step(good, params, tstate, buf)   # must not raise


def test_train_step_host_callback_rejected():
    """A host callback anywhere in the update re-serializes serving and
    training (the step overlaps the fused decide dispatch)."""
    rp_mod, buf, params, tstate = _train_fixture()

    def chatty(params, tstate, replay, rng):
        batch = rp_mod.sample_device(replay, rng, 8)
        jax.debug.callback(lambda r: None, batch["rewards"])
        return jnp.sum(batch["rewards"] * batch["valid"])

    with pytest.raises(ContractViolation) as ei:
        check_train_step(chatty, params, tstate, buf)
    assert "callback-in-scan" in str(ei.value)


def test_real_trainer_step_accepted():
    """The shipped OnlineTrainer step passes its own construction gate
    (contract_check=True is the default — this builds one for real)."""
    from repro.core.reward import energy_reward_spec as _ers
    from repro.runtime.trainer import OnlineTrainer
    pred = Predictor(linear_policy(F, A),
                     _ers(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.full(A, -1.0), np.full(A, 1.0)),
                     E, F, replay_capacity=8)
    OnlineTrainer(pred, batch_size=4, contract_check=True)


# ---------------------------------------------------------------------------
# construction-time gates: RewardSpec and PerceptaSystem
# ---------------------------------------------------------------------------

def test_reward_spec_checks_custom_terms_at_construction():
    with pytest.raises(ContractViolation) as ei:
        RewardSpec((RewardTerm("custom",
                               fn=lambda f, a, p: f[:, 0] / f[:, 0].sum()),))
    assert "env-reduce" in str(ei.value)


def test_reward_spec_unchecked_escape_hatch(caplog):
    term = RewardTerm("custom", fn=lambda f, a, p: f[:, 0] / f[:, 0].sum())
    with caplog.at_level(logging.INFO, logger="repro.core.reward"):
        spec = RewardSpec((term,), unchecked=True)
    assert spec.terms == (term,)
    assert any("unchecked" in r.message for r in caplog.records)


def test_untraceable_custom_term_warns_not_raises():
    """A fn indexing past every probe shape is deferred (with a warning)
    to the true-shape check at system construction."""
    needs_777 = lambda f, a, p: f.reshape(f.shape[0], 777)[:, 0]
    with pytest.warns(UserWarning, match="could not statically check"):
        check_reward_terms((RewardTerm("custom", fn=needs_777),))


def _mini_system(mode, policy, **kw):
    from repro.core import PipelineConfig
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import PerceptaSystem, SourceSpec
    srcs = [SourceSpec("meter", "mqtt",
                       SimulatedDevice("grid_kw", 60.0, base=3.0, seed=1)),
            SourceSpec("price", "http",
                       SimulatedDevice("price_eur", 300.0, base=0.2, seed=2))]
    cfg = PipelineConfig(n_envs=2, n_streams=2, n_ticks=8, max_samples=32)
    pred = Predictor(policy,
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     2, cfg.n_features, replay_capacity=8)
    return PerceptaSystem(["bldg-0", "bldg-1"], srcs, cfg, pred,
                          speedup=5000.0, manual_time=True, mode=mode,
                          scan_k=2, **kw)


def test_system_gate_rejects_gemm_policy_in_sharded_fused():
    W = jnp.ones((2, 2))
    bad = ModelAdapter(lambda f: f @ W, name="gemm")
    with pytest.raises(ContractViolation) as ei:
        _mini_system("scan_fused_decide_sharded", bad)
    msg = str(ei.value)
    assert "env-gemm-rows" in msg and "dot_general" in msg


def test_system_gate_accepts_linear_policy_in_sharded_fused():
    sys_ = _mini_system("scan_fused_decide_sharded", linear_policy(2, 2))
    results = sys_.run_windows(2)
    sys_.stop()
    assert len(results) == 2


def test_system_gate_env_rules_off_outside_sharded_dispatch():
    """A gemm policy is legal where the decision math is not env-sharded:
    the fused non-sharded engine, and scan_sharded's host-side consume."""
    W = jnp.ones((2, 2))
    bad = ModelAdapter(lambda f: f @ W, name="gemm")
    for mode in ("scan_fused_decide", "scan_sharded"):
        sys_ = _mini_system(mode, bad)
        sys_.stop()


def test_system_gate_opt_out():
    W = jnp.ones((2, 2))
    bad = ModelAdapter(lambda f: f @ W, name="gemm")
    sys_ = _mini_system("scan_fused_decide_sharded", bad,
                        contract_check=False)
    sys_.stop()


# ---------------------------------------------------------------------------
# AST lint: one bad/good fixture pair per rule
# ---------------------------------------------------------------------------

def _lint_src(src, rel="src/repro/core/fixture.py", tmp_path=None):
    p = tmp_path / "fixture.py"
    p.write_text(src)
    return lint_mod.lint_file(str(p), rel=rel)


def _rules(violations):
    return sorted({v.rule for v in violations})


def test_lint_jax_version_branch(tmp_path):
    bad = ("import jax\n"
           "if jax.__version__.startswith('0.4'):\n    x = 1\n")
    good = "import jax\nprint('running jax', jax.__version__)\n"
    assert _rules(_lint_src(bad, tmp_path=tmp_path)) == ["jax-version-branch"]
    assert _lint_src(good, tmp_path=tmp_path) == []
    # compat.py owns the version seam
    assert _lint_src(bad, rel="src/repro/compat.py", tmp_path=tmp_path) == []


def test_lint_jax_experimental(tmp_path):
    bad = "from jax.experimental.shard_map import shard_map\n"
    good = "from jax.experimental import pallas as pl\n"
    assert _rules(_lint_src(bad, tmp_path=tmp_path)) == \
        ["jax-experimental-outside-compat"]
    assert _lint_src(good, tmp_path=tmp_path) == []
    assert _lint_src(bad, rel="src/repro/compat.py", tmp_path=tmp_path) == []


def test_lint_mesh_calls(tmp_path):
    bad = ("from jax.sharding import Mesh\n"
           "mesh = Mesh(devs, ('data',))\n")
    good = ("from repro import compat\n"
            "import jax\n"
            "def f(m: jax.sharding.Mesh):\n"      # typing ref: fine
            "    return compat.make_mesh(devs, ('data',))\n")
    assert _rules(_lint_src(bad, tmp_path=tmp_path)) == ["mesh-outside-compat"]
    assert _lint_src(good, tmp_path=tmp_path) == []


def test_lint_donate_routing(tmp_path):
    bad = "import jax\nstep = jax.jit(f, donate_argnums=(0,))\n"
    good = ("from repro import compat\n"
            "step = compat.jit_donated(f, donate_argnums=(0,))\n")
    assert _rules(_lint_src(bad, tmp_path=tmp_path)) == \
        ["donate-outside-compat"]
    assert _lint_src(good, tmp_path=tmp_path) == []


def test_lint_state_leaf_alias(tmp_path):
    bad = "norm = system.state.norm\n"
    good = "norm = system.snapshot_norm()\n"
    assert _rules(_lint_src(bad, tmp_path=tmp_path)) == ["state-leaf-alias"]
    assert _lint_src(good, tmp_path=tmp_path) == []
    # runtime/system.py itself owns the state and is exempt
    assert _lint_src(bad, rel="src/repro/runtime/system.py",
                     tmp_path=tmp_path) == []


def test_lint_async_donate(tmp_path):
    rt = "src/repro/runtime/fixture.py"
    bad_lit = "out = dispatch(batch, donate=True)\n"
    bad_mode = ("out = dispatch(batch, donate=mode in "
                "('scan', 'scan_async'))\n")
    good = "out = dispatch(batch, donate=mode in ('scan', 'scan_sharded'))\n"
    assert _rules(_lint_src(bad_lit, rel=rt, tmp_path=tmp_path)) == \
        ["async-donate"]
    assert _rules(_lint_src(bad_mode, rel=rt, tmp_path=tmp_path)) == \
        ["async-donate"]
    assert _lint_src(good, rel=rt, tmp_path=tmp_path) == []
    # outside runtime/ the rule does not bind
    assert _lint_src(bad_lit, tmp_path=tmp_path) == []


def test_lint_lock_multi_acquire(tmp_path):
    rt = "src/repro/runtime/fixture.py"
    bad = ("def flush(self, items):\n"
           "    for it in items:\n"
           "        with self._lock:\n"
           "            self._emit(it)\n")
    good = ("def flush(self, items):\n"
            "    with self._lock:\n"
            "        for it in items:\n"
            "            self._emit(it)\n")
    sibling = ("class Hub:\n"
               "    def emit(self, it):\n"
               "        with self._lock:\n"
               "            self.sink.append(it)\n"
               "    def flush(self, items):\n"
               "        with self._lock:\n"
               "            self.emit(items[0])\n")
    assert _rules(_lint_src(bad, rel=rt, tmp_path=tmp_path)) == \
        ["lock-multi-acquire"]
    assert _lint_src(good, rel=rt, tmp_path=tmp_path) == []
    assert _rules(_lint_src(sibling, rel=rt, tmp_path=tmp_path)) == \
        ["lock-multi-acquire"]
    # a daemon's `while not stopped:` poll loop legitimately locks per wake
    daemon = ("def pump(self):\n"
              "    while not self._stop:\n"
              "        with self._lock:\n"
              "            self._drain()\n")
    assert _lint_src(daemon, rel=rt, tmp_path=tmp_path) == []


def test_lint_pragma_suppression(tmp_path):
    src = ("import jax\n"
           "if jax.__version__.startswith('0.4'):  # lint: allow[jax-version-branch]\n"
           "    x = 1\n")
    assert _lint_src(src, tmp_path=tmp_path) == []
    above = ("import jax\n"
             "# lint: allow[jax-version-branch]\n"
             "if jax.__version__.startswith('0.4'):\n    x = 1\n")
    assert _lint_src(above, tmp_path=tmp_path) == []
    # pragma for a different rule does not suppress
    wrong = ("import jax\n"
             "if jax.__version__.startswith('0.4'):  # lint: allow[async-donate]\n"
             "    x = 1\n")
    assert _rules(_lint_src(wrong, tmp_path=tmp_path)) == \
        ["jax-version-branch"]


def test_lint_baseline_roundtrip(tmp_path):
    p = tmp_path / "fixture.py"
    p.write_text("import jax\nstep = jax.jit(f, donate_argnums=(0,))\n")
    base = tmp_path / "baseline.json"
    found = lint_mod.lint_file(str(p), rel=str(p))
    assert len(found) == 1
    # before a baseline exists: everything is new
    new, old = lint_mod.apply_baseline(found, str(base))
    assert (len(new), len(old)) == (1, 0)
    lint_mod.write_baseline(found, str(base))
    # fingerprint survives a line-number shift (rule+file+code, not lineno)
    p.write_text("import jax\n\n\nstep = jax.jit(f, donate_argnums=(0,))\n")
    moved = lint_mod.lint_file(str(p), rel=str(p))
    new, old = lint_mod.apply_baseline(moved, str(base))
    assert (len(new), len(old)) == (0, 1)
    data = json.loads(base.read_text())
    assert data["violations"][0]["rule"] == "donate-outside-compat"


def test_repo_is_lint_clean():
    """The committed tree carries zero un-baselined findings — the same
    pin `make lint` enforces in CI (the baseline is committed empty)."""
    paths = [os.path.join(REPO, p) for p in lint_mod.DEFAULT_PATHS]
    paths = [p for p in paths if os.path.exists(p)]
    new, old = lint_mod.apply_baseline(lint_mod.run_paths(paths),
                                       lint_mod.DEFAULT_BASELINE)
    assert new == [], "\n".join(v.format() for v in new)
    assert old == []          # baseline is empty: nothing grandfathered


def test_rule_catalogs_cover_engines():
    """Every rule either engine can emit is declared in contracts.py (the
    catalog the ROADMAP table and --list-rules mirror)."""
    assert set(JAXPR_RULES) == {
        "env-contraction", "env-gemm-rows", "env-reduce", "collective",
        "time-cast", "callback-in-scan", "reward-shape", "carry-env-mix",
        "pallas-env-block", "param-replication", "env-mask-gate"}
    assert set(LINT_RULES) == {
        "jax-version-branch", "jax-experimental-outside-compat",
        "mesh-outside-compat", "donate-outside-compat", "state-leaf-alias",
        "async-donate", "lock-multi-acquire"}
    assert lint_mod.main(["--list-rules"]) == 0
