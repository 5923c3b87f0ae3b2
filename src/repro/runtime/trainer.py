"""OnlineTrainer — device-resident policy retraining that overlaps the
fused decide scan.

Percepta's retraining loop, closed ON DEVICE: PR 5 made the replay ring
device-resident, but learning from it still required ``export_replay``'s
full host round-trip (ring -> numpy -> optimizer -> new weights -> rebuild
the system). This module wires ``replay.sample_device`` (in-place minibatch
gather) and ``train/optimizer.py`` (AdamW + global-norm clip) into ONE
jitted update step and interleaves it with the fused decide dispatches
using the async machinery from PR 3:

    boundary j:   apply_pending()      # adopt step j-2's result, bump
                                       #   policy_version, swap the carry
                  decide dispatch j    # donates carry j-1 -> carry j
                  dispatch(carry j)    # train step enqueues AFTER decide
                                       #   j on the same device stream
    (host consumes batch j-1 / assembles batch j+1 meanwhile)

The single CPU/TPU device queue executes in order, so the train step runs
in the dispatch bubble while the host is busy consuming — serving pays no
extra dispatch latency (bench cell ii). Dispatching the train step AFTER
the decide scan avoids the priority inversion PR 3 hit (a train step
enqueued first would delay the serving batch behind it).

Donation discipline (the double-donation hazard): the train step reads
``dstate.policy`` and ``dstate.replay`` — the LIVE carry leaves the next
decide dispatch will donate — so it must NOT donate them. It donates only
argnum 1, the trainer-owned train state (critic + joint optimizer state),
which nothing else references. By the time decide j+1 donates carry j, the train step
holding references to carry j's buffers is already enqueued; the runtime
keeps those buffers alive until it completes.

Hot-swap is race-free and versioned: a swap replaces the ``policy`` /
``version`` leaves of the decide carry at a batch boundary only (between
two dispatches, never mid-scan), ``policy_version`` increments
monotonically on every APPLIED update, and the decide path stamps the
producing version into every replay row and LogDB row — each K-batch is
attributable to exactly one policy.

Empty-ring safety: ``sample_device`` gates on ``size == 0`` with a
``valid`` mask; the update additionally gates the new params / optimizer
state on ``has_data`` inside the jit, so a step dispatched before the
first transition banks is an exact no-op (no AdamW weight-decay drift, no
step-count advance, no version bump).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import TrainConfig
from repro.core import f32
from repro.core import replay as rp
from repro.train import optimizer as opt
from repro.train.checkpoint import Checkpointer


def critic_init(n_features: int, n_actions: int) -> dict:
    """Linear reward critic ``Q(obs, act) = [obs; act] . w + b`` (the
    trainer-owned half of the update — it never enters the decide carry)."""
    return {"qw": jnp.zeros((n_features + n_actions,), jnp.float32),
            "qb": jnp.zeros((), jnp.float32)}


def critic_apply(critic, obs, actions):
    x = jnp.concatenate([obs, actions], axis=-1)
    return f32.einsum("...i,i->...", x, critic["qw"]) + critic["qb"]


def td_loss(apply_fn, params, critic, batch, pi_coef: float = 0.1):
    """One-step TD/regression loss on a sampled minibatch.

    Two coupled terms (DDPG-shaped, contextual-bandit horizon):

      * critic regression against the BANKED rewards:
        ``(Q(obs, banked_action) - reward)^2`` — the "regression loss
        against banked rewards" half; and
      * policy improvement through the critic:
        ``-Q(obs, policy(obs))`` — the deterministic-policy-gradient half
        (note a pure behaviour-cloning loss would be vacuous here: the
        deterministic policy reproduces its own banked actions exactly,
        so its gradient is identically zero).

    Every term is masked by ``valid`` (see ``replay.sample_device``) and
    normalized by the valid count, floored so an all-invalid batch yields
    loss 0 with zero gradients.
    """
    v = batch["valid"].astype(jnp.float32)
    nv = jnp.maximum(jnp.sum(v), 1.0)
    q_banked = critic_apply(critic, batch["obs"], batch["actions"])
    loss_q = jnp.sum(v * jnp.square(q_banked - batch["rewards"])) / nv
    a_pi = apply_fn(params, batch["obs"])
    loss_pi = -jnp.sum(v * critic_apply(critic, batch["obs"], a_pi)) / nv
    return loss_q + pi_coef * loss_pi


def default_train_cfg(**overrides) -> TrainConfig:
    """Online-policy defaults: no warmup (the first applied step should
    move), no weight decay (a deployed policy must not drift toward zero
    while the ring is sparse)."""
    kw = dict(learning_rate=3e-4, warmup_steps=0, weight_decay=0.0)
    kw.update(overrides)
    return TrainConfig(**kw)


class OnlineTrainer:
    """Interleaves jitted policy updates with the fused decide dispatches.

    Protocol (driven by ``PerceptaSystem`` at each batch boundary, in this
    order — see the module docstring's timeline):

      * :meth:`apply_pending` BEFORE the decide dispatch: adopt the
        previous train step's result; if it saw data, bump
        ``policy_version`` and return the carry with the new
        ``policy``/``version`` leaves swapped in (otherwise return it
        unchanged). Also snapshots policy+opt state through the async
        :class:`Checkpointer` every ``checkpoint_every`` applied steps.
      * :meth:`dispatch` AFTER the decide dispatch: enqueue one train step
        on the new carry's (non-donated) policy and replay ring.

    Standalone use (benchmarks, tests): ``step_fn(params, train_state,
    replay, rng)`` is the jitted update — donating ONLY ``train_state``
    (critic + joint optimizer state) — returning ``(new_params,
    new_train_state, loss, gnorm, has_data)``.
    """

    def __init__(self, predictor, batch_size: int = 128,
                 train_cfg: Optional[TrainConfig] = None, seed: int = 0,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, contract_check: bool = True):
        from repro.runtime.predictor import policy_call

        apply_fn, params = policy_call(predictor.model)
        if not jax.tree.leaves(params):
            raise ValueError(
                "online training needs a parameterized model: give the "
                "ModelAdapter params= and apply= (see linear_policy); "
                f"model '{predictor.model.name}' exposes no trainable "
                "params")
        self.predictor = predictor
        self.batch_size = int(batch_size)
        self.cfg = train_cfg if train_cfg is not None else default_train_cfg()
        critic = critic_init(predictor.n_features,
                             predictor.replay.actions.shape[-1])
        # trainer-owned state: the critic never rides the decide carry, and
        # one optimizer state covers the joint {policy, critic} tree
        self.train_state = {
            "critic": critic,
            "opt": opt.init({"policy": params, "critic": critic}),
        }
        self.version = int(predictor.policy_version)
        self.stats = {"dispatched": 0, "applied": 0, "skipped_empty": 0,
                      "last_loss": None, "last_gnorm": None}
        self._rng = jax.random.PRNGKey(seed)
        self._pending = None
        cfg = self.cfg

        def train_step(params, tstate, replay, rng):
            batch = rp.sample_device(replay, rng, self.batch_size)
            # any() not [0]: under an elastic mask individual cells can be
            # invalid (detached-slot rows) while the ring still has data
            has_data = jnp.any(batch["valid"])
            joint = {"policy": params, "critic": tstate["critic"]}
            loss, grads = jax.value_and_grad(
                lambda pc: td_loss(apply_fn, pc["policy"], pc["critic"],
                                   batch))(joint)
            new_joint, new_opt, gnorm = opt.update(grads, tstate["opt"],
                                                   joint, cfg)
            # gate on has_data INSIDE the jit: with an empty ring the
            # gradients are zero but AdamW's weight decay / step advance
            # would still perturb params — the no-op must be exact
            gate = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(has_data, a, b), new, old)
            new_tstate = {"critic": gate(new_joint["critic"],
                                         tstate["critic"]),
                          "opt": gate(new_opt, tstate["opt"])}
            return (gate(new_joint["policy"], params), new_tstate,
                    jnp.where(has_data, loss, 0.0), gnorm, has_data)

        if contract_check:
            from repro import analysis
            analysis.check_train_step(train_step, params, self.train_state,
                                      predictor.replay,
                                      label="OnlineTrainer.train_step")
        # donate ONLY the trainer-owned opt state (argnum 1) — params and
        # replay are live decide-carry leaves the next serving dispatch
        # donates (module docstring: the double-donation hazard)
        self.step_fn = compat.jit_donated(train_step, donate_argnums=(1,))
        self._ckpt = None
        self.checkpoint_every = int(checkpoint_every)
        if checkpoint_dir is not None:
            self._ckpt = Checkpointer(checkpoint_dir,
                                      keep=self.cfg.keep_checkpoints,
                                      async_mode=self.cfg.async_checkpoint)

    # --- batch-boundary protocol ------------------------------------------

    def apply_pending(self, dstate):
        """Adopt the in-flight train result; swap the carry at the boundary.

        Host-syncs on one scalar (``has_data``) — the step was enqueued
        right after the PREVIOUS decide dispatch, which has since been
        consumed, so it has already run. Returns ``dstate`` with the new
        ``policy``/``version`` leaves when the step applied, unchanged
        otherwise. The optimizer state is adopted either way (its old
        buffer was donated into the step)."""
        if self._pending is None:
            return dstate
        new_params, new_tstate, loss, gnorm, has_data = self._pending
        self._pending = None
        self.train_state = new_tstate
        if not bool(has_data):
            self.stats["skipped_empty"] += 1
            return dstate
        self.stats["applied"] += 1
        self.stats["last_loss"] = float(loss)
        self.stats["last_gnorm"] = float(gnorm)
        self.version += 1
        # the carry's reference to new_params is donated into the next
        # decide dispatch (sync modes); the host mirror and the checkpoint
        # must hold their own buffers
        host_params = jax.tree.map(lambda x: jnp.array(x, copy=True),
                                   new_params)
        self.predictor.adopt_policy(host_params, self.version)
        self._maybe_checkpoint(host_params)
        return dstate._replace(
            policy=new_params, version=jnp.asarray(self.version, jnp.int32))

    def dispatch(self, dstate) -> None:
        """Enqueue one train step behind the decide dispatch that produced
        ``dstate`` (non-donating reads of its policy/replay leaves)."""
        self._rng, sub = jax.random.split(self._rng)
        self._pending = self.step_fn(dstate.policy, self.train_state,
                                     dstate.replay, sub)
        self.stats["dispatched"] += 1

    def flush_pending(self, dstate):
        """Drain the in-flight step (end of run / before export)."""
        return self.apply_pending(dstate)

    # --- checkpointing ----------------------------------------------------

    def _maybe_checkpoint(self, params) -> None:
        if self._ckpt is None or self.checkpoint_every <= 0:
            return
        if self.stats["applied"] % self.checkpoint_every == 0:
            self._ckpt.save(
                self.stats["applied"],
                {"params": params, "train": self.train_state},
                extra={"policy_version": self.version,
                       "applied": self.stats["applied"]})

    def save_checkpoint(self, block: bool = True) -> int:
        """Snapshot policy+opt state now; returns the step saved at."""
        if self._ckpt is None:
            raise ValueError("OnlineTrainer built without checkpoint_dir")
        step = self.stats["applied"]
        self._ckpt.save(step,
                        {"params": self.predictor.policy_params,
                         "train": self.train_state},
                        extra={"policy_version": self.version,
                               "applied": step},
                        block=block)
        return step

    def restore_latest(self):
        """Restore the newest policy+opt snapshot into the trainer and the
        predictor's host mirror; returns ``(step, params, extra)`` or
        ``None`` when no checkpoint exists.

        This restores the HOST side only. In a running fused system the
        serving weights live in the device carry — use
        ``PerceptaSystem.restore_training()``, which calls this and then
        swaps the restored policy/version leaves into the carry; a fresh
        ``predictor.decide_state()`` also picks the weights up (both
        crash-recovery paths are exercised in tests/test_trainer.py).
        """
        if self._ckpt is None:
            raise ValueError("OnlineTrainer built without checkpoint_dir")
        self._ckpt.flush()
        step = self._ckpt.latest_step()
        if step is None:
            return None
        # an in-flight step trained on the pre-restore weights: discard it
        # (its donated train_state is replaced wholesale below)
        self._pending = None
        like = {"params": self.predictor.policy_params,
                "train": self.train_state}
        tree, extra = self._ckpt.restore(step, like)
        self.train_state = tree["train"]
        self.version = int(extra.get("policy_version", self.version))
        self.stats["applied"] = int(extra.get("applied",
                                              self.stats["applied"]))
        self.predictor.adopt_policy(tree["params"], self.version)
        return step, tree["params"], extra

    def close(self) -> None:
        if self._ckpt is not None:
            self._ckpt.close()

    def train_stats(self) -> dict:
        return dict(self.stats, version=self.version)
