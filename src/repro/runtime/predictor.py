"""Predictor — routes features to the decision model, validates actions,
computes rewards, logs for retraining, hands decisions to Forwarders.

The model is pluggable (``ModelAdapter``): a vector policy (edge RL), an
LM-family model through a TokenCodec, or anything callable on (E, F)
features. This is the "support any type of AI model that consumes this
data" requirement.

Three consume paths:

  * :meth:`Predictor.on_tick` — one jitted ``_step`` per window. The
    per-window reference path; fused mode and the bit-identity tests use
    it.
  * :meth:`Predictor.on_windows` — a K-window stack in ONE jitted
    dispatch: the policy and action validation run under ``lax.scan`` (so
    every window executes exactly the per-window (E, F) gemm), the
    ``prev_obs``/``prev_actions``/``have_prev`` carry materializes as
    shifted stacks, reward terms evaluate K-leading in one shot
    (elementwise over the stack, see ``RewardSpec.compute``), and the K
    replay transitions append through ``replay.add_many`` (itself a
    ``lax.scan`` carrying the buffer — exact sequential ring semantics).
    Outputs are bit-identical to K sequential ``on_tick`` calls; the
    scan-mode Manager consume uses this path so the decision side of the
    system costs one device dispatch per K windows, like the pipeline.
  * :meth:`Predictor.make_decide_fn` — the fully fused path
    (``mode="scan_fused_decide"``): a pure per-window decision step the
    pipeline scan body calls directly, with :class:`DecideState` (prev
    obs/actions, have_prev, the exact tick counter, and the
    :class:`~repro.core.replay.ReplayBuffer`) carried ON DEVICE inside
    the same donated scan carry as the pipeline state. The Predictor
    object then holds no live replay/prev state — the system owns the
    carry, :meth:`absorb_fused` keeps the host-side stats/time mirror in
    sync per batch, and replay export goes through the system's
    non-donating snapshot (``PerceptaSystem.export_replay``). The step
    runs exactly the per-window ops of ``_step``, so fused outputs are
    bit-identical to both reference consume paths.

Long-horizon time rule (mirrors the scan engine's window-relative rebase):
the replay buffer stores the EXACT int32 tick index per transition, never a
float32 absolute time — consecutive window ends quantize to the same
float32 value past t~2^24 s. The absolute float64 time of every tick is
mirrored host-side in ``_replay_times`` (slot-aligned with the device
ring) and re-attached at export by :meth:`export_replay`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import replay as rp
from repro.core.reward import RewardSpec, validate_actions


@dataclass
class ActionSpace:
    low: np.ndarray
    high: np.ndarray

    @property
    def n(self):
        return len(self.low)


class DecideState(NamedTuple):
    """Device-resident decision carry for the fused scan engine.

    Lives inside the same donated/env-sharded carry pytree as the pipeline
    state: ``prev_obs``/``prev_actions`` and every replay-ring row shard on
    the env dim, the scalars (``have_prev``, ``tick``, the ring cursor)
    replicate, and the ``policy`` params subtree replicates explicitly
    (weights are batch-global, not per-env rows — see
    ``sharding.decide_specs``). ``tick`` is the EXACT int32 predictor tick
    index of the next window — the long-horizon time rule's device half;
    absolute float64 times are reconstructed host-side at export. Only the
    small prev/tick/policy part rides the per-window ``lax.scan`` carry;
    the replay ring is written once per batch by the ``bank`` half of
    :class:`DecideFns` (threading the (E, C, F) storage through the scan
    carry measured a full ring copy per dispatch).

    ``policy`` is the live policy-params pytree for parameterized models
    (``{}`` for closure-only models), and ``version``/``prev_version``
    carry the monotone policy_version attribution: ``version`` names the
    policy producing THIS batch's actions, ``prev_version`` the one that
    produced ``prev_actions`` (they differ exactly on the first window
    after a hot-swap). Swaps happen host-side at batch boundaries only
    (``runtime.trainer.OnlineTrainer``), so every K-batch is attributable
    to exactly one policy.

    ``carry`` is the OPTIONAL recurrent model state of a stateful policy
    (``ModelAdapter.apply_carry``/``init_carry`` — e.g. the registry's
    ``rglru``/``rwkv6`` models): ``None`` (a leafless pytree — invisible
    to the scan carry, donation and the spec trees) for stateless
    policies, otherwise a pytree of per-env ``(E, ...)`` leaves the env
    mesh shards on dim 0 by the ``env_specs`` rank rule.  The
    certification pass (``repro.analysis.certify``) proves every carry
    leaf is env-row-stable (``carry-env-mix``) before a stateful policy
    may ride the fused/sharded engines.

    ``active``/``prev_ok`` are the ELASTIC slot-pool mask leaves: ``None``
    (leafless — dense pytrees, traces, specs and donation are unchanged)
    for fixed-E systems; under ``PerceptaSystem(elastic=True)`` they are
    (E,) bool carry leaves sharded on the env axis like every row block.
    ``active`` marks which slots are live THIS batch (the decide step
    gates its outputs on it by select; the host flips values between
    batches — no retrace); ``prev_ok`` is the per-env twin of the scalar
    ``have_prev`` chain — True once a slot has produced a window since it
    last attached — gating the batch's first banked transition per row.
    """
    prev_obs: jax.Array      # (E, F)
    prev_actions: jax.Array  # (E, A)
    have_prev: jax.Array     # () bool
    tick: jax.Array          # () int32
    replay: rp.ReplayBuffer
    policy: dict             # params pytree ({} when not hot-swappable)
    version: jax.Array       # () int32 — policy_version of ``policy``
    prev_version: jax.Array  # () int32 — version that made prev_actions
    carry: object = None     # recurrent model state (None = stateless)
    active: object = None    # (E,) bool slot mask (None = dense fixed-E)
    prev_ok: object = None   # (E,) bool per-env have-prev (None = dense)


class DecideFns(NamedTuple):
    """The fused engine's decision protocol (see ``make_decide_fn``).

    ``step(DecideState, FeatureFrame) -> (DecideState, (actions, reward,
    per_term, violated), transition)`` runs one window's decision math
    inside the scan body (the carried ``replay`` field passes through
    untouched — it may be ``None`` there); ``transition`` is the
    ``(prev_obs, prev_actions, reward, next_obs, tick, version,
    have_prev)`` row the window banks (7 flat trailing outputs — the
    arity ``analysis.check_decide_fns`` keys on). ``bank(ReplayBuffer,
    stacked transitions, env_mask=None) -> ReplayBuffer`` writes the
    whole batch after the scan in one exact ring scatter
    (``replay.add_batch``); ``env_mask`` (K, E) bool is the elastic
    per-row liveness landing in the ring's ``valid`` column.
    """
    step: Callable
    bank: Callable


class ModelAdapter:
    """Wraps any policy fn(features (E,F)) -> actions (E,A).

    Parameterized models additionally expose ``params`` (a trainable
    pytree) and ``apply(params, features) -> actions``, with
    ``fn == apply(params, .)``. The fused engine then threads the weights
    as an EXPLICIT input (the ``DecideState.policy`` carry leaf) instead
    of a traced-in closure constant, which is what makes race-free policy
    hot-swap possible without retracing: the trainer replaces the carry
    leaf at a batch boundary and the already-compiled scan runs the new
    weights. Closure-only models (``params is None``) keep the old
    behaviour and are not hot-swappable.

    RECURRENT models (the registry's ``rglru``/``rwkv6``) instead expose
    ``apply_carry(params, features, carry) -> (actions, new_carry)`` plus
    ``init_carry(n_envs) -> carry`` (a pytree of per-env ``(E, ...)``
    leaves). Their state threads through every consume path's scan carry
    (and ``DecideState.carry`` on the fused engines); ``fn`` may be
    ``None`` — there is no stateless view to call.
    """

    def __init__(self, fn: Optional[Callable], name: str = "policy",
                 params=None, apply: Optional[Callable] = None,
                 apply_carry: Optional[Callable] = None,
                 init_carry: Optional[Callable] = None):
        if apply_carry is not None and init_carry is None:
            raise ValueError(
                f"stateful policy '{name}': apply_carry requires "
                "init_carry(n_envs) so every consume path can materialize "
                "the recurrent state at the system's env count")
        self.fn = fn
        self.name = name
        self.params = params
        self.apply = apply
        self.apply_carry = apply_carry
        self.init_carry = init_carry

    def __call__(self, features):
        if self.fn is None:
            raise TypeError(
                f"policy '{self.name}' is stateful (apply_carry) and has "
                "no stateless fn view — call apply_carry(params, features, "
                "carry) or route it through a Predictor consume path")
        return self.fn(features)


def policy_call(model):
    """``(apply_fn, params)`` view of a STATELESS model.

    Parameterized adapters route their weights explicitly; closure-only
    models get an empty params pytree and an apply that ignores it — both
    shapes trace to the same per-window ops, so fused outputs stay
    bit-identical to the reference paths either way.

    Stateful (``apply_carry``) models are rejected here: callers of this
    view (e.g. ``runtime.trainer.OnlineTrainer``'s train step) cannot
    thread a recurrent carry, so offering them a carry-less apply would
    silently re-run the policy from blank state every call.
    """
    if getattr(model, "apply_carry", None) is not None:
        raise ValueError(
            f"policy '{getattr(model, 'name', model)}' is stateful "
            "(apply_carry): the stateless (apply, params) view cannot "
            "thread its recurrent carry — use policy_call2 / the decide "
            "paths; online retraining (train='online') supports stateless "
            "policies only")
    if getattr(model, "apply", None) is not None \
            and getattr(model, "params", None) is not None:
        return model.apply, model.params
    return (lambda params, feats: model(feats)), {}


def policy_call2(model):
    """``(apply2, params, init_carry)`` view — the carry-capable calling
    convention every Predictor consume path traces.

    ``apply2(params, features, carry) -> (actions, new_carry)``. Stateless
    models wrap with a pass-through carry (``None`` in, ``None`` out, a
    leafless pytree — invisible to scans/donation/spec trees) and
    ``init_carry is None``; stateful adapters pass their ``apply_carry``
    through unchanged. One convention means one trace shape everywhere,
    so stateless policies cost nothing for the generality.
    """
    if getattr(model, "apply_carry", None) is not None:
        params = getattr(model, "params", None)
        return model.apply_carry, ({} if params is None else params), \
            model.init_carry
    apply_fn, params = policy_call(model)

    def apply2(p, feats, carry):
        return apply_fn(p, feats), carry

    return apply2, params, None


def host_params(init: Callable[[], dict]) -> dict:
    """Run the weight initializer ``init`` on the host CPU backend and
    return its pytree as numpy arrays.

    ``jax.random.normal``'s inverse-erf transform is implemented per
    backend, and one key gave MLP weights up to 1.1e-5 apart on a TPU v5e
    and on the CPU. Drawn on the host, a seed is the same model on every
    backend.
    """
    with jax.default_device(jax.devices("cpu")[0]):
        return jax.tree.map(np.asarray, init())


def linear_policy(n_features: int, n_actions: int, seed: int = 0,
                  low=-1.0, high=1.0) -> ModelAdapter:
    """A small deterministic policy standing in for the deployed RL model.

    The policy dot is phrased as multiply+reduce over F rather than
    ``feats @ W``: under the env-sharded fused engine each device sees
    E/N feature rows, and XLA:CPU lowers the (rows, F) x (F, A) dot
    through row-count-dependent kernels inside the fused scan (1-ulp
    divergence between the sharded and full-E programs). The reduce
    form's per-element add order depends only on F, so the same bits come
    out at every shard size — the property the fused-sharded mode's
    bit-identity guarantee rests on (a custom model must preserve it too
    to compose with ``mode="scan_fused_decide_sharded"``).
    """
    params = host_params(lambda: {
        "w": jax.random.normal(jax.random.PRNGKey(seed),
                               (n_features, n_actions))
        / jnp.sqrt(n_features)})

    def apply(params, feats):
        logits = (feats[..., :, None] * params["w"][None, :, :]).sum(-2)
        return jnp.tanh(logits) * (high - low) / 2 + (high + low) / 2

    # construction-time snapshot for direct ``model(feats)`` callers; the
    # runtime paths route through (apply, params) and see hot-swapped weights
    fn = jax.jit(lambda feats: apply(params, feats))
    return ModelAdapter(fn, "linear_policy", params=params, apply=apply)


class Predictor:
    def __init__(self, model, reward_spec: RewardSpec,
                 action_space: ActionSpace, n_envs: int, n_features: int,
                 db=None, replay_capacity: int = 4096):
        self.reward_spec = reward_spec
        self.action_space = action_space
        # recorded so the construction-time contract checker
        # (repro.analysis.check_system) can probe the decide path at the
        # true (E, F) shapes without re-deriving them from the pipeline
        self.n_envs = n_envs
        self.n_features = n_features
        self.db = db
        self.replay = rp.init(n_envs, replay_capacity, n_features,
                              action_space.n)
        # host-side float64 absolute-time mirror, slot-aligned with the
        # device ring: the transition written at cursor c (tick index c+1)
        # lives in slot c % capacity of both structures
        self._replay_times = np.zeros((replay_capacity,), np.float64)
        self._prev = {
            "obs": jnp.zeros((n_envs, n_features), jnp.float32),
            "actions": jnp.zeros((n_envs, action_space.n), jnp.float32),
            "have": False,
            "version": 0,  # policy_version that produced prev_actions
        }
        self.stats = {"ticks": 0, "violations": 0}
        self.policy_version = 0
        self.set_model(model)

    def set_model(self, model) -> None:
        """Bind (or rebind) the decision model and (re)build the jitted
        consume paths around it.

        ``model`` may be a prebuilt :class:`ModelAdapter`, a registry name
        (``"linear" | "mlp" | "rglru" | "rwkv6"``) or a
        ``runtime.policies.PolicyConfig`` — names/configs resolve through
        the certified registry (``runtime.policies.build_policy``), so a
        registry policy arrives with its
        :class:`~repro.analysis.certify.PolicyCertificate` attached.
        Rebinding resets the recurrent model carry (if any) to its
        ``init_carry`` state; replay/stats/prev are untouched.
        """
        if isinstance(model, str) or type(model).__name__ == "PolicyConfig":
            from repro.runtime.policies import build_policy
            model = build_policy(model, self.n_features,
                                 self.action_space.n, self.n_envs)
        self.model = model
        # (apply2, params, init_carry) view: parameterized models thread
        # weights as explicit jit inputs on EVERY consume path (reference
        # and fused) — one calling convention traces everywhere, hot-swapped
        # weights reuse the compiled programs without retracing, and
        # stateful models thread their recurrent carry the same way
        apply2, params0, init_carry = policy_call2(model)
        self._apply2 = apply2
        self.policy_params = params0
        # host mirror of the recurrent model state (None for stateless
        # policies); the fused engines carry it in DecideState.carry
        self._model_carry = (init_carry(self.n_envs)
                             if init_carry is not None else None)
        low = jnp.asarray(self.action_space.low, jnp.float32)
        high = jnp.asarray(self.action_space.high, jnp.float32)

        def _step(features, raw, prev_obs, prev_actions, replay, tick_idx,
                  have_prev, params, version, mcarry, active=None,
                  prev_ok=None):
            actions, new_mcarry = apply2(params, features, mcarry)
            actions, violated = validate_actions(actions, low, high)
            # rewards are computed on engineering units, not z-scores
            reward, per_term = self.reward_spec.compute(
                raw, actions, prev_actions)
            if active is not None:
                # elastic slot pool: gate outputs by select (active rows
                # bit-exact, inactive rows deterministic zeros) and mark
                # only rows that close a real prev->next pair valid
                actions = jnp.where(active[:, None], actions, 0.0)
                reward = jnp.where(active, reward, 0.0)
                per_term = jnp.where(active[:, None], per_term, 0.0)
                violated = active & violated
                row_ok = active & prev_ok
            else:
                row_ok = None
            new_replay = jax.lax.cond(
                have_prev,
                lambda r: rp.add(r, prev_obs, prev_actions, reward, features,
                                 tick_idx, version, env_mask=row_ok),
                lambda r: r,
                replay)
            return actions, reward, per_term, violated, new_replay, new_mcarry

        self._step = jax.jit(_step)

        def _steps(features, raw, tick_idx, prev_obs, prev_actions,
                   have_prev, replay, params, version, prev_version, mcarry,
                   active=None, prev_ok=None):
            """K windows in one dispatch. The policy/validate scan runs the
            SAME per-window (E, F) computation ``_step`` jits (a batched
            K-leading gemm could block/accumulate differently on some
            backends, breaking bit-identity with the reference path) and
            threads the recurrent model carry exactly as K sequential
            steps would; the carried prev obs/actions materialize as the
            shifted stacks below, so reward terms — elementwise over the
            stack — evaluate K-leading in one shot."""
            def body(mc, f):
                actions, mc = apply2(params, f, mc)
                actions, violated = validate_actions(actions, low, high)
                return mc, (actions, violated)

            mcarry_out, (actions, violated) = jax.lax.scan(
                body, mcarry, features)
            if active is not None:
                # elastic slot pool: gate per-window outputs by select
                # BEFORE the prev-chain materializes, so inactive rows
                # carry deterministic zeros into the shifted stacks (the
                # barrier seals the policy math from the select's fusion
                # — see make_decide_fn)
                actions, violated = jax.lax.optimization_barrier(
                    (actions, violated))
                actions = jnp.where(active[None, :, None], actions, 0.0)
                violated = active[None, :] & violated
                # trailing fence: the masked actions feed the reward
                # compute below — the select must not fuse into it either
                actions, violated = jax.lax.optimization_barrier(
                    (actions, violated))
            prev_act_seq = jnp.concatenate([prev_actions[None], actions[:-1]],
                                           0)
            rewards, per_term = self.reward_spec.compute(raw, actions,
                                                         prev_act_seq)
            if active is not None:
                rewards, per_term = jax.lax.optimization_barrier(
                    (rewards, per_term))
                rewards = jnp.where(active[None, :], rewards, 0.0)
                per_term = jnp.where(active[None, :, None], per_term, 0.0)
            # transition j stores (obs/actions entering window j, reward j,
            # next_obs = window j's features); only the first row of the
            # batch can lack a predecessor — and only row 0's banked action
            # can carry a different (earlier) policy_version
            K = features.shape[0]
            prev_obs_seq = jnp.concatenate([prev_obs[None], features[:-1]], 0)
            mask = jnp.concatenate([have_prev[None],
                                    jnp.ones((K - 1,), jnp.bool_)])
            ver_seq = jnp.concatenate(
                [prev_version[None], jnp.full((K - 1,), version, jnp.int32)])
            if active is not None:
                # per-row liveness: window 0 closes a pair begun last
                # batch (needs the per-env prev_ok), later windows need
                # only active — membership is constant within a batch
                E = features.shape[1]
                rows = jnp.broadcast_to(active[None, :], (K, E))
                env_mask = jnp.concatenate(
                    [(active & prev_ok)[None, :], rows[1:]], axis=0)
            else:
                env_mask = None
            new_replay = rp.add_many(replay, prev_obs_seq, prev_act_seq,
                                     rewards, features, tick_idx, mask,
                                     ver_seq, env_mask=env_mask)
            return (actions, rewards, per_term, violated, features[-1],
                    actions[-1], new_replay, mcarry_out)

        self._steps = jax.jit(_steps)

    # --- fused decision path (mode="scan_fused_decide") --------------------
    def decide_state(self) -> DecideState:
        """Materialize the current decision state as the device carry the
        fused scan engine threads (and donates) between batches. Taking it
        hands ownership to the caller: from here on the Predictor's own
        ``replay``/``_prev``/``_model_carry`` references are a stale
        snapshot of this moment — export through the system's non-donating
        snapshot."""
        return DecideState(
            prev_obs=jnp.asarray(self._prev["obs"], jnp.float32),
            prev_actions=jnp.asarray(self._prev["actions"], jnp.float32),
            have_prev=jnp.asarray(bool(self._prev["have"])),
            tick=jnp.asarray(self.stats["ticks"], jnp.int32),
            replay=self.replay,
            policy=self.policy_params,
            version=jnp.asarray(self.policy_version, jnp.int32),
            prev_version=jnp.asarray(self._prev["version"], jnp.int32),
            carry=self._model_carry,
        )

    def adopt_policy(self, params, version: int) -> None:
        """Sync the Predictor's host-side policy mirror after a fused-carry
        hot-swap (the live weights travel in ``DecideState.policy``; this
        keeps ``policy_params``/``policy_version`` — and any later
        ``decide_state()`` rebuild — consistent with the device carry)."""
        self.policy_params = params
        if getattr(self.model, "params", None) is not None:
            self.model.params = params
        self.policy_version = int(version)

    def make_decide_fn(self) -> DecideFns:
        """Decision protocol for the fused pipeline scan (:class:`DecideFns`).

        The ``step`` half runs exactly the per-window ops of the jitted
        ``_step`` (policy on the (E, F) features, validate, rewards on
        engineering units with the carried prev actions) and emits the
        window's replay transition row at the carried exact tick index;
        the ``bank`` half writes the K stacked rows in one unique-indices
        ring scatter (``replay.add_batch`` — final contents bit-identical
        to K sequential guarded ``add`` calls, without the ring ever
        riding the scan carry). Everything is per-env row-wise — both
        halves run unchanged under the env-sharded ``shard_map`` build
        (custom reward fns and models must be row-wise too; see
        ``linear_policy`` for the shard-size-invariant dot phrasing)."""
        low = jnp.asarray(self.action_space.low, jnp.float32)
        high = jnp.asarray(self.action_space.high, jnp.float32)
        apply2, spec = self._apply2, self.reward_spec

        def step(carry: DecideState, feats):
            actions, new_mcarry = apply2(carry.policy, feats.features,
                                         carry.carry)
            actions, violated = validate_actions(actions, low, high)
            reward, per_term = spec.compute(feats.raw, actions,
                                            carry.prev_actions)
            if carry.active is not None:
                # elastic slot pool: combine the mask by select only
                # (active rows keep their exact bits; inactive rows
                # become deterministic zeros) — the env-mask-gate rule
                # rejects any row-compacting alternative. The barrier
                # stops XLA fusing the selects into the reward reduction
                # epilogue (changed fusion re-contracts multiply-adds:
                # 1-ulp drift vs the dense build on XLA:CPU)
                act = carry.active
                actions, reward, per_term, violated = \
                    jax.lax.optimization_barrier(
                        (actions, reward, per_term, violated))
                actions = jnp.where(act[:, None], actions, 0.0)
                reward = jnp.where(act, reward, 0.0)
                per_term = jnp.where(act[:, None], per_term, 0.0)
                violated = act & violated
            # transition entering this window: only bankable once a
            # predecessor exists (the mask the bank applies); it is
            # attributed to the version that produced its ACTION —
            # carry.prev_version, which trails carry.version by exactly the
            # first window after a batch-boundary hot-swap
            transition = (carry.prev_obs, carry.prev_actions, reward,
                          feats.features, carry.tick, carry.prev_version,
                          carry.have_prev)
            new = DecideState(prev_obs=feats.features, prev_actions=actions,
                              have_prev=jnp.ones((), jnp.bool_),
                              tick=carry.tick + 1, replay=carry.replay,
                              policy=carry.policy, version=carry.version,
                              prev_version=carry.version, carry=new_mcarry,
                              active=carry.active, prev_ok=carry.prev_ok)
            return new, (actions, reward, per_term, violated), transition

        def bank(replay, transitions, env_mask=None):
            obs, actions, rewards, next_obs, tick, version, mask = transitions
            return rp.add_batch(replay, obs, actions, rewards, next_obs,
                                tick, mask, version, env_mask=env_mask)

        return DecideFns(step, bank)

    def absorb_fused(self, tick_times, violated) -> None:
        """Post-consume host bookkeeping for one fused batch: advance the
        tick/violation stats and the slot-aligned float64 time mirror in
        lockstep with the device carry (which advanced by ``len(
        tick_times)`` inside the dispatch). The mirror stays maintained so
        mirror-based and reconstructed exports agree; the fused export
        itself reconstructs times from ``tick_idx`` (see
        ``PerceptaSystem.export_replay``)."""
        base = self.stats["ticks"]
        self._record_times(base, tick_times)
        self.stats["ticks"] += len(tick_times)
        self.stats["violations"] += int(np.asarray(violated).sum())

    def _record_times(self, base_idx: int, tick_times) -> None:
        """Mirror absolute float64 tick times into the slot-aligned host
        ring (tick idx adds at cursor idx-1 -> slot (idx-1) % capacity)."""
        C = self.replay.capacity
        for j, t in enumerate(tick_times):
            idx = base_idx + j
            if idx >= 1:
                self._replay_times[(idx - 1) % C] = float(t)

    def on_tick(self, features, tick_time, raw=None, active=None,
                prev_ok=None):
        """features: (E, F) device array; returns host actions + rewards.

        The per-window reference path — :meth:`on_windows` must stay
        bit-identical to K calls of this. ``active``/``prev_ok`` (E,) bool
        are the elastic slot-pool masks (None = dense)."""
        raw = features if raw is None else raw
        idx = self.stats["ticks"]
        (actions, reward, per_term, violated, self.replay,
         self._model_carry) = self._step(
            features, raw, self._prev["obs"], self._prev["actions"],
            self.replay, jnp.asarray(idx, jnp.int32),
            jnp.asarray(self._prev["have"]), self.policy_params,
            jnp.asarray(self._prev["version"], jnp.int32),
            self._model_carry,
            None if active is None else jnp.asarray(active, jnp.bool_),
            None if prev_ok is None else jnp.asarray(prev_ok, jnp.bool_))
        self._record_times(idx, [tick_time])
        self._prev = {"obs": features, "actions": actions, "have": True,
                      "version": self.policy_version}
        self.stats["ticks"] += 1
        self.stats["violations"] += int(np.asarray(violated).sum())
        return np.asarray(actions), np.asarray(reward), np.asarray(per_term)

    def on_windows(self, features, tick_times, raw=None, active=None,
                   prev_ok=None):
        """Consume a K-window stack in ONE jitted dispatch.

        ``features``/``raw``: (K, E, F) (raw defaults to features);
        ``tick_times``: K absolute window-end times (host float64, never
        sent to device). Returns host ``(actions (K, E, A), rewards (K, E),
        per_term (K, E, n_terms))`` — bit-identical to K sequential
        :meth:`on_tick` calls, including replay contents and stats.
        ``active``/``prev_ok`` (E,) bool are the elastic slot-pool masks
        (None = dense; membership is constant within a batch).
        """
        features = jnp.asarray(features)
        raw = features if raw is None else jnp.asarray(raw)
        K = features.shape[0]
        assert K >= 1 and len(tick_times) == K, (K, len(tick_times))
        base = self.stats["ticks"]
        tick_idx = jnp.asarray(base + np.arange(K), jnp.int32)
        (actions, rewards, per_term, violated, last_obs, last_actions,
         self.replay, self._model_carry) = self._steps(
            features, raw, tick_idx, self._prev["obs"],
            self._prev["actions"], jnp.asarray(self._prev["have"]),
            self.replay, self.policy_params,
            jnp.asarray(self.policy_version, jnp.int32),
            jnp.asarray(self._prev["version"], jnp.int32),
            self._model_carry,
            None if active is None else jnp.asarray(active, jnp.bool_),
            None if prev_ok is None else jnp.asarray(prev_ok, jnp.bool_))
        self._record_times(base, tick_times)
        self._prev = {"obs": last_obs, "actions": last_actions, "have": True,
                      "version": self.policy_version}
        self.stats["ticks"] += K
        self.stats["violations"] += int(np.asarray(violated).sum())
        return np.asarray(actions), np.asarray(rewards), np.asarray(per_term)

    def export_replay(self, env_ids, salt: str) -> dict:
        """Anonymized chronological replay export with exact float64
        absolute times reconstructed from the host-side mirror."""
        return rp.export_for_training(self.replay, env_ids, salt,
                                      slot_times=self._replay_times)

    # --- elastic slot-pool hooks (PerceptaSystem(elastic=True)) ------------
    def clear_env_rows(self, slots) -> None:
        """Scrub env rows for recycled slots (scan-mode attach/detach):
        zero the prev carry rows and invalidate every replay cell of the
        slot, so a later tenant of the same row never observes — or banks
        against — the departed env's transitions. Out-of-place ``.at``
        updates between dispatches, so donation aliasing is never
        violated."""
        slots = np.asarray(slots, np.int64).reshape(-1)
        if slots.size == 0:
            return
        self._prev["obs"] = jnp.asarray(self._prev["obs"]).at[slots].set(0.0)
        self._prev["actions"] = \
            jnp.asarray(self._prev["actions"]).at[slots].set(0.0)
        self.replay = self.replay._replace(
            valid=self.replay.valid.at[slots].set(False))
        if self._model_carry is not None and self.model.init_carry is not None:
            tmpl = self.model.init_carry(self.n_envs)
            self._model_carry = jax.tree.map(
                lambda x, t: jnp.asarray(x).at[slots].set(
                    jnp.asarray(t)[slots]),
                self._model_carry, tmpl)

    def grow_envs(self, n_envs_new: int) -> None:
        """Pad the env axis of every per-env structure to ``n_envs_new``
        slots (elastic pool regrow). New rows come from a FRESH init
        template — never raw zeros — and existing rows are byte-for-byte
        preserved, so surviving envs resume bit-exactly."""
        from repro.distribution import elastic as el

        old_e = self.n_envs
        assert n_envs_new > old_e, (n_envs_new, old_e)
        self.n_envs = n_envs_new
        tmpl_replay = rp.init(n_envs_new, self.replay.capacity,
                              self.n_features, self.action_space.n)
        self.replay = el.grow_env_tree(self.replay, tmpl_replay, old_e)
        prev_tmpl = {
            "obs": jnp.zeros((n_envs_new, self.n_features), jnp.float32),
            "actions": jnp.zeros((n_envs_new, self.action_space.n),
                                 jnp.float32),
        }
        self._prev["obs"] = el.grow_env_tree(
            jnp.asarray(self._prev["obs"]), prev_tmpl["obs"], old_e)
        self._prev["actions"] = el.grow_env_tree(
            jnp.asarray(self._prev["actions"]), prev_tmpl["actions"], old_e)
        if self._model_carry is not None and self.model.init_carry is not None:
            self._model_carry = el.grow_env_tree(
                self._model_carry, self.model.init_carry(n_envs_new), old_e)
