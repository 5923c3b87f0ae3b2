"""Certified policy registry (PR 8): ``runtime.policies`` +
``analysis.certify``.

Every registered policy (linear / mlp / rglru / rwkv6) must statically
certify against the FULL rule catalog — row-wise env math, recurrent-carry
row stability across the decide-step fixed point, pallas BlockSpec env
routing, param replication — and then run the fused/sharded engines
bit-identical to the unsharded per-window reference, stateful carries
riding ``DecideState.carry``. Bad builders (gemm phrasing, cross-env
carries, env-sized params, cross-env pallas index maps) are rejected AT
REGISTRATION with rule, primitive and source named.
"""
import functools
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import certify as certify_mod
from repro.analysis.certify import PolicyCertificate, certify_policy
from repro.analysis.contracts import ContractViolation
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.distribution import sharding
from repro.runtime.policies import (POLICIES, PolicyConfig, build_policy,
                                    rglru_builder)
from repro.runtime.predictor import (ActionSpace, ModelAdapter, Predictor,
                                     policy_call, policy_call2)
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.system import PerceptaSystem, SourceSpec

E, F, A = 4, 6, 2
STATEFUL = ("rglru", "rwkv6")


def _predictor(model, n_envs=E, n_features=F, cap=16):
    return Predictor(model,
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.full(A, -1.0), np.full(A, 1.0)),
                     n_envs, n_features, replay_capacity=cap)


def _system(mode, policy, n_envs=2, scan_k=3, **kw):
    srcs = [SourceSpec("meter", "mqtt",
                       SimulatedDevice("grid_kw", 60.0, base=3.0, seed=1)),
            SourceSpec("price", "http",
                       SimulatedDevice("price_eur", 300.0, base=0.2,
                                       amplitude=0.05, seed=2))]
    cfg = PipelineConfig(n_envs=n_envs, n_streams=2, n_ticks=8, tick_s=60.0,
                         max_samples=32)
    pred = _predictor(policy, n_envs=n_envs, n_features=cfg.n_features)
    return PerceptaSystem([f"b{i}" for i in range(n_envs)], srcs, cfg, pred,
                          speedup=5000.0, manual_time=True, mode=mode,
                          scan_k=scan_k, **kw)


def _strip(results):
    return [{k: v for k, v in r.items() if k != "latency_s"}
            for r in results]


# --------------------------------------------------------------------------
# registry + certification happy path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(POLICIES))
def test_registry_policy_certifies_with_certificate_attached(name):
    adapter = build_policy(name, F, A, E)
    cert = adapter.certificate
    assert isinstance(cert, PolicyCertificate)
    assert cert.name == name
    assert cert.stateful == (name in STATEFUL)
    # full-strictness certification: every rule family was enforced
    assert set(cert.rules) == {"env", "collectives", "callbacks", "time",
                               "carry"}
    assert cert.param_spec and cert.jaxpr_sha256
    if name == "rglru":
        assert "'h'" in cert.carry_treedef
    if name == "rwkv6":
        assert "'wkv'" in cert.carry_treedef


@pytest.mark.parametrize("name", sorted(POLICIES))
def test_registry_weights_drawn_on_host(name):
    # jax.random.normal differs per backend in its last digits: the
    # weights are host arrays, drawn on the CPU, so a seed is one model
    a = build_policy(name, F, A, E).params
    b = build_policy(name, F, A, E).params
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert type(x) is np.ndarray and x.dtype == np.float32
        np.testing.assert_array_equal(x, y)


def test_certificate_cache_skips_retracing():
    certify_mod.clear_cache()
    a = build_policy("mlp", F, A, E)
    t0 = time.perf_counter()
    b = build_policy("mlp", F, A, E)
    cached_s = time.perf_counter() - t0
    # identical certificate OBJECT: the second standup hit the cache (and
    # paid dict-lookup time, not a re-trace)
    assert b.certificate is a.certificate
    assert cached_s < 0.5


def test_unknown_policy_name_rejected():
    with pytest.raises(KeyError, match="Unrecognized policy .*registered"):
        build_policy("transformer9000", F, A, E)


def test_policy_config_kwargs_flow_to_builder():
    adapter = build_policy(PolicyConfig("rglru", {"hidden": 8}), F, A, E)
    assert adapter.init_carry(E)["h"].shape == (E, 8)
    assert adapter.certificate.stateful


def test_rglru_pallas_kernel_is_certifiable():
    """The pallas_call path certifies — BlockSpec index maps are mapped
    onto the env tag instead of conservatively poisoning the outputs."""
    cert = certify_policy(functools.partial(rglru_builder, use_pallas=True),
                          name="rglru")
    assert cert.stateful


# --------------------------------------------------------------------------
# bad builders rejected at registration, with rule + primitive + source
# --------------------------------------------------------------------------

def _gemm_builder(n_features, n_actions, n_envs=None, **kw):
    W = jnp.ones((n_features, n_actions)) / n_features

    def apply(p, f):
        return jnp.tanh(f @ p["w"])          # the banned gemm phrasing

    return ModelAdapter(lambda f: apply({"w": W}, f), "gemm",
                        params={"w": W}, apply=apply)


def test_gemm_policy_rejected_naming_rule_primitive_source():
    with pytest.raises(ContractViolation) as ei:
        certify_policy(_gemm_builder, name="bad-gemm")
    msg = str(ei.value)
    assert "env-gemm-rows" in msg and "dot_general" in msg
    assert "test_policies.py:" in msg          # source line named
    # satellite: the diagnostic names the registry key AND the builder —
    # never a bare "<lambda>"
    assert "policy 'bad-gemm'" in msg and "_gemm_builder" in msg


def test_lambda_partial_builder_diagnostics_name_builder():
    """functools.partial-wrapped builders unwrap to the underlying fn in
    the diagnostic label (a partial has no __name__ of its own)."""
    bound = functools.partial(_gemm_builder)
    with pytest.raises(ContractViolation) as ei:
        certify_policy(bound, name="bad-gemm-partial")
    msg = str(ei.value).splitlines()[0]
    assert "policy 'bad-gemm-partial'" in msg
    assert "_gemm_builder" in msg


def _roll_carry_builder(n_features, n_actions, n_envs=None, **kw):
    W = jnp.ones((n_features, n_actions)) / n_features

    def apply_carry(p, f, c):
        # row i's new state depends on row i-1's old state: cross-env
        h = jnp.roll(c["h"], 1, axis=0) \
            + (f[..., :, None] * p["w"][None]).sum(-2)
        return jnp.tanh(h), {"h": h}

    return ModelAdapter(None, "roll_carry", params={"w": W},
                        apply_carry=apply_carry,
                        init_carry=lambda E: {"h": jnp.zeros((E, n_actions))})


def test_cross_env_carry_rejected_naming_rule_primitive():
    with pytest.raises(ContractViolation) as ei:
        certify_policy(_roll_carry_builder, name="bad-carry")
    msg = str(ei.value)
    assert "carry-env-mix" in msg
    # the jnp.roll lowering (concatenate of shifted slices) is named with
    # its source line
    assert "concatenate" in msg or "slice" in msg
    assert "test_policies.py:" in msg


def _env_params_builder(n_features, n_actions, n_envs=4, **kw):
    W = jnp.ones((n_envs, n_features, n_actions)) / n_features

    def apply(p, f):
        return (f[..., :, None] * p["w"]).sum(-2)

    return ModelAdapter(lambda f: apply({"w": W}, f), "env_params",
                        params={"w": W}, apply=apply)


def test_env_sized_params_rejected_naming_leaf():
    with pytest.raises(ContractViolation) as ei:
        certify_policy(_env_params_builder, name="bad-params")
    msg = str(ei.value)
    assert "param-replication" in msg and "'w'" in msg
    assert "decide_specs" in msg


def _bad_pallas_builder(n_features, n_actions, n_envs=None, **kw):
    from jax.experimental import pallas as pl_mod

    W = jnp.ones((n_features, n_actions)) / n_features

    def kernel(h_ref, o_ref):
        o_ref[...] = h_ref[...] * 2.0

    def apply_carry(p, f, c):
        h = c["h"]
        nE, H = h.shape
        hp = jnp.pad(h, ((0, 0), (0, 128 - H)))
        # input index map reads the REVERSED env block: instance i reads
        # env row nE-1-i but writes env row i
        out = pl_mod.pallas_call(
            kernel, grid=(nE, 1),
            in_specs=[pl_mod.BlockSpec((1, 128),
                                       lambda bi, wi: (nE - 1 - bi, wi))],
            out_specs=pl_mod.BlockSpec((1, 128), lambda bi, wi: (bi, wi)),
            out_shape=jax.ShapeDtypeStruct((nE, 128), jnp.float32),
            interpret=True)(hp)
        h2 = out[:, :H] + (f[..., :, None] * p["w"][None]).sum(-2)
        return jnp.tanh(h2), {"h": h2}

    return ModelAdapter(None, "bad_pallas", params={"w": W},
                        apply_carry=apply_carry,
                        init_carry=lambda E: {"h": jnp.zeros((E, n_actions))})


def test_cross_env_pallas_index_map_rejected():
    with pytest.raises(ContractViolation) as ei:
        certify_policy(_bad_pallas_builder, name="bad-pallas")
    msg = str(ei.value)
    assert "pallas-env-block" in msg and "pallas_call" in msg
    assert "test_policies.py:" in msg


# --------------------------------------------------------------------------
# stateful policies through the consume paths
# --------------------------------------------------------------------------

def test_stateless_view_rejects_stateful_models():
    """``policy_call`` (the OnlineTrainer's view) refuses apply_carry
    models — online retraining supports stateless policies only."""
    adapter = build_policy("rglru", F, A, E)
    with pytest.raises(ValueError, match="stateful.*stateless"):
        policy_call(adapter)
    with pytest.raises(TypeError, match="stateful"):
        adapter(jnp.zeros((E, F)))           # no stateless __call__ either
    apply2, params, init_carry = policy_call2(adapter)
    acts, carry = apply2(params, jnp.zeros((E, F)), init_carry(E))
    assert acts.shape == (E, A)


def test_predictor_accepts_registry_name_and_threads_carry():
    pred = _predictor("rglru")
    assert pred.model.certificate is not None
    feats = jnp.asarray(np.random.RandomState(0)
                        .normal(size=(E, F)).astype(np.float32))
    pred.on_tick(feats, 60.0)
    c1 = np.asarray(pred._model_carry["h"])
    pred.on_tick(feats * 0.5, 120.0)
    c2 = np.asarray(pred._model_carry["h"])
    assert (c1 != 0).any() and (c1 != c2).any()   # carry actually advances
    # rebinding resets the recurrent state
    pred.set_model("mlp")
    assert pred._model_carry is None


def test_on_windows_matches_on_tick_for_stateful_policy():
    """The K-window batched consume threads the model carry through its
    inner scan exactly as K sequential per-window steps."""
    rng = np.random.RandomState(1)
    feats = rng.normal(size=(6, E, F)).astype(np.float32)
    times = [60.0 * (j + 1) for j in range(6)]
    p_ref = _predictor("rwkv6")
    p_bat = _predictor("rwkv6")
    ref = [p_ref.on_tick(jnp.asarray(feats[j]), times[j]) for j in range(6)]
    acts, rews, per = p_bat.on_windows(jnp.asarray(feats), times)
    for j in range(6):
        assert (np.asarray(ref[j][0]) == np.asarray(acts[j])).all()
        assert (np.asarray(ref[j][1]) == np.asarray(rews[j])).all()
    for a, b in zip(jax.tree.leaves(p_ref._model_carry),
                    jax.tree.leaves(p_bat._model_carry)):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("name", sorted(POLICIES))
def test_fused_and_sharded_modes_match_per_window_reference(name):
    """System level, every registered policy: fused decide (and the
    degenerate 1-device sharded build) == the per-window on_tick
    reference, stateful carry riding ``DecideState.carry``."""
    ref = _system("scan", name, batched_consume=False)
    fus = _system("scan_fused_decide", name)
    shd = _system("scan_fused_decide_sharded", name)
    rr = _strip(ref.run_windows(7))
    assert rr == _strip(fus.run_windows(7))
    assert rr == _strip(shd.run_windows(7))
    assert fus.policy_certificate is not None
    for s in (ref, fus, shd):
        s.stop()


def test_rglru_pallas_bit_parity_through_fused_decide():
    """``use_pallas=True`` (interpreter-mode kernel) and the lax.scan
    reference produce bit-identical actions through the fused engine."""
    a = _system("scan_fused_decide",
                PolicyConfig("rglru", {"use_pallas": False}))
    b = _system("scan_fused_decide",
                PolicyConfig("rglru", {"use_pallas": True}))
    ra, rb = a.run_windows(5), b.run_windows(5)
    assert _strip(ra) == _strip(rb)
    for x, y in zip(jax.tree.leaves(a.snapshot_decide().carry),
                    jax.tree.leaves(b.snapshot_decide().carry)):
        assert (np.asarray(x) == np.asarray(y)).all()
    a.stop(), b.stop()


def test_decide_specs_shard_model_carry_on_env_dim():
    """The recurrent carry's (E, ...) leaves pick up the env sharding by
    the ``env_specs`` rank rule; the policy params stay replicated."""
    from jax.sharding import PartitionSpec as P

    pred = _predictor("rwkv6")
    specs = sharding.decide_specs(pred.decide_state(), 0)
    assert specs.carry["shift"] == P("data", None)
    assert specs.carry["wkv"] == P("data", None, None)
    assert all(s == P() for s in jax.tree.leaves(specs.policy))


def test_online_training_refuses_stateful_policy():
    with pytest.raises(ValueError, match="stateless"):
        _system("scan_fused_decide", "rglru", train="online")


# --------------------------------------------------------------------------
# acceptance regime: E=256 on the real 8-device mesh (subprocess)
# --------------------------------------------------------------------------

_SHARDED_SCRIPT = """
import numpy as np
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.policies import POLICIES
from repro.runtime.predictor import ActionSpace, Predictor
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.system import PerceptaSystem, SourceSpec
import jax
assert len(jax.devices()) == 8, jax.devices()

E = 256

def mk(mode, policy):
    srcs = [SourceSpec("meter", "mqtt",
                       SimulatedDevice("grid_kw", 60.0, base=3.0, seed=1)),
            SourceSpec("price", "http",
                       SimulatedDevice("price_eur", 300.0, base=0.2,
                                       amplitude=0.05, seed=2))]
    cfg = PipelineConfig(n_envs=E, n_streams=2, n_ticks=4, tick_s=60.0,
                         max_samples=16)
    pred = Predictor(policy,
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     E, cfg.n_features, replay_capacity=8)
    return PerceptaSystem([f"b{i}" for i in range(E)], srcs, cfg, pred,
                          speedup=5000.0, manual_time=True, mode=mode,
                          scan_k=3, **({"batched_consume": False}
                                       if mode == "scan" else {}))

strip = lambda rs: [{k: v for k, v in r.items() if k != "latency_s"}
                    for r in rs]
for policy in sorted(POLICIES):
    ref = mk("scan", policy)                 # per-window on_tick reference
    rr = strip(ref.run_windows(5))
    s = mk("scan_fused_decide_sharded", policy)
    assert dict(s.pipeline.mesh.shape) == {"data": 8}, s.pipeline.mesh
    assert s.policy_certificate is not None, policy
    assert strip(s.run_windows(5)) == rr, policy
    ea, eb = ref.export_replay("s"), s.export_replay("s")
    for k in ("obs", "actions", "rewards", "next_obs", "tick_idx", "times"):
        assert (np.asarray(ea[k]) == np.asarray(eb[k])).all(), (policy, k)
    ref.stop(), s.stop()
    print(policy, "OK")
print("POLICY_SHARDED_OK")
"""


def test_registry_policies_sharded_e256_bit_identical():
    """Every registered policy at E=256 on the forced 8-device mesh:
    ``scan_fused_decide_sharded`` == the unsharded per-window reference,
    bit for bit, replay export included — stateful carries env-sharded on
    dim 0 of ``DecideState.carry``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "POLICY_SHARDED_OK" in out.stdout


# --------------------------------------------------------------------------
# lint surfaces (satellite: machine-readable output + CI annotations)
# --------------------------------------------------------------------------

def test_lint_json_format(tmp_path, capsys):
    import json

    from repro.analysis import lint as lint_mod

    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "if jax.__version__ >= '0.5':\n"
                   "    x = 1\n")
    rc = lint_mod.main([str(bad), "--no-baseline", "--format=json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["new"] == 1
    (f,) = doc["findings"]
    assert f["rule"] == "jax-version-branch"
    assert f["file"].endswith("bad.py") and f["line"] == 2
    assert f["fingerprint"]["code"].startswith("if jax.__version__")


def test_lint_github_format_emits_per_line_annotations(tmp_path, capsys):
    from repro.analysis import lint as lint_mod

    bad = tmp_path / "bad.py"
    bad.write_text("from jax.experimental import mesh_utils\n")
    rc = lint_mod.main([str(bad), "--no-baseline", "--format=github"])
    out = capsys.readouterr().out
    assert rc == 1
    line = [l for l in out.splitlines() if l.startswith("::error")][0]
    assert "file=" in line and "line=1" in line
    assert "jax-experimental-outside-compat" in line


def test_lint_stage_with_registry_certification_under_30s():
    """The whole ``make lint`` stage — AST lint + builtin jaxpr checks +
    certification of every registered policy — stays under 30 s."""
    from repro.analysis import lint as lint_mod

    t0 = time.perf_counter()
    rc = lint_mod.main(["--jaxpr-builtins"])
    dt = time.perf_counter() - t0
    assert rc == 0
    assert dt < 30.0, f"lint stage took {dt:.1f}s"
