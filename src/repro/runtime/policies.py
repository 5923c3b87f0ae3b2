"""Certified policy registry — REAL decision models for the fused/sharded
decision path, keyed by name.

``PerceptaSystem(..., policy="rglru")`` (or ``Predictor(model="rglru",
...)``) resolves here: :func:`build_policy` looks the name up in
:data:`POLICIES`, statically certifies the builder against the full
:mod:`repro.analysis` rule catalog (:func:`repro.analysis.certify_policy` —
env row-wise math, shard-size-invariant dot phrasing, recurrent-carry
row stability, pallas BlockSpec env routing, param replication) and only
then builds the :class:`~repro.runtime.predictor.ModelAdapter`, attaching
the :class:`~repro.analysis.certify.PolicyCertificate` the fused/sharded
system modes demand at construction. Certification is cached by
``(name, kwargs, probe shapes)``, so repeated standups of the same policy
skip re-tracing entirely.

Every registered model obeys the bit-identity contract of the env-sharded
fused engine (see ``linear_policy``): per-env row-wise math only, with
every dot phrased as multiply+reduce over the contracted dim
(:func:`_rowdot`) so rounding is independent of rows-per-device. The
recurrent models keep their state in per-env ``(E, ...)`` carry leaves
(``DecideState.carry``) — row i's state stays in row i, the
``carry-env-mix`` invariant — and are single-step re-phrasings of the
sequence models in :mod:`repro.models` (``models/rglru.py``,
``models/rwkv6.py``): same gate math, T=1, env rows as the batch.

Registry idiom: a frozen :class:`PolicyConfig` (name + kwargs) dispatching
through a dict of builders, ``KeyError`` on unknown names.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from repro.analysis.certify import certify_policy
from repro.runtime.predictor import ModelAdapter, host_params, linear_policy


def _rowdot(x, w):
    """Per-row dot contracted by multiply+reduce: ``x (..., F) @ w (F, H)``
    without ``dot_general``.

    The add order depends only on the contracted dim, never on the row
    count, so the same bits come out at every shard size — XLA:CPU's gemm
    kernels are row-count-dependent (1-ulp drift per shard size), which is
    why the env-gemm-rows rule bans ``@`` on env rows outright.
    """
    return (x[..., :, None] * w[None]).sum(-2)


def _scale(logits, low, high):
    return jnp.tanh(logits) * (high - low) / 2 + (high + low) / 2


# --------------------------------------------------------------------------
# builders — builder(n_features, n_actions, n_envs=E, **kwargs) -> adapter
# --------------------------------------------------------------------------

def linear_builder(n_features: int, n_actions: int, n_envs: int = None,
                   seed: int = 0, low=-1.0, high=1.0) -> ModelAdapter:
    """The deployed linear policy (``runtime.predictor.linear_policy``)."""
    del n_envs  # stateless and env-count independent
    return linear_policy(n_features, n_actions, seed=seed, low=low, high=high)


def mlp_builder(n_features: int, n_actions: int, n_envs: int = None,
                hidden: int = 32, seed: int = 0,
                low=-1.0, high=1.0) -> ModelAdapter:
    """Two-layer gated MLP (SwiGLU), stateless and row-wise."""
    del n_envs
    def init():
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        return {
            "w1": jax.random.normal(k1, (n_features, hidden))
            / jnp.sqrt(n_features),
            "w3": jax.random.normal(k2, (n_features, hidden))
            / jnp.sqrt(n_features),
            "w2": jax.random.normal(k3, (hidden, n_actions))
            / jnp.sqrt(hidden),
        }
    params = host_params(init)

    def apply(params, feats):
        h = _rowdot(feats, params["w1"])
        g = _rowdot(feats, params["w3"])
        return _scale(_rowdot(jax.nn.silu(g) * h, params["w2"]), low, high)

    fn = jax.jit(lambda feats: apply(params, feats))
    return ModelAdapter(fn, "mlp_policy", params=params, apply=apply)


def rglru_builder(n_features: int, n_actions: int, n_envs: int = None,
                  hidden: int = 16, seed: int = 0, low=-1.0, high=1.0,
                  use_pallas: bool = False) -> ModelAdapter:
    """Recurrent RG-LRU policy — the single-step, env-rows-as-batch
    re-phrasing of ``models/rglru.py``'s gate math, with the recurrence
    update running through the ``kernels/rglru_scan`` op at T=1 (the
    ``lax.scan`` reference by default; ``use_pallas=True`` routes the
    Pallas kernel, whose BlockSpec env routing the certifier checks
    instead of conservatively poisoning).

    Carry: ``{"h": (E, hidden)}`` — per-env hidden state on dim 0.
    """
    from repro.kernels.rglru_scan import ops

    del n_envs  # carry is built by init_carry at the system's env count
    def init():
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        return {
            "w_in": jax.random.normal(ks[0], (n_features, hidden))
            / jnp.sqrt(n_features),
            "w_a": jax.random.normal(ks[1], (hidden,)) * 0.1,
            "b_a": jnp.zeros((hidden,)),
            "w_i": jax.random.normal(ks[2], (hidden,)) * 0.1,
            "b_i": jnp.zeros((hidden,)),
            # softplus(lam) in (0, 1)-ish: forget rates spread over the units
            "lam": jnp.linspace(-2.0, 1.0, hidden),
            "w_out": jax.random.normal(ks[3], (hidden, n_actions))
            / jnp.sqrt(hidden),
        }
    params = host_params(init)

    def apply_carry(params, feats, carry):
        h = carry["h"]                                   # (E, H)
        u = _rowdot(feats, params["w_in"])               # (E, H)
        r = jax.nn.sigmoid(u * params["w_a"][None] + params["b_a"][None])
        i = jax.nn.sigmoid(u * params["w_i"][None] + params["b_i"][None])
        log_a = -8.0 * jax.nn.softplus(params["lam"])[None] * r
        gated = i * u
        b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated
        # one step of h' = a*h + b through the shared scan op (B=E, T=1)
        _, h_new = ops.rglru_scan(jnp.exp(log_a)[:, None, :],
                                  b[:, None, :], h, use_pallas=use_pallas)
        actions = _scale(_rowdot(h_new, params["w_out"]), low, high)
        return actions, {"h": h_new}

    def init_carry(n_envs):
        return {"h": jnp.zeros((n_envs, hidden), jnp.float32)}

    return ModelAdapter(None, "rglru_policy", params=params,
                        apply_carry=apply_carry, init_carry=init_carry)


def rwkv6_builder(n_features: int, n_actions: int, n_envs: int = None,
                  hidden: int = 8, seed: int = 0,
                  low=-1.0, high=1.0) -> ModelAdapter:
    """Recurrent RWKV-6 policy — the single-head, single-step re-phrasing
    of ``models/rwkv6.py``'s ``time_mix_step`` (token shift + data-dependent
    decay + wkv state), env rows as the batch and the attention einsum
    re-phrased as multiply+reduce for shard-size-invariant bits.

    Carry: ``{"shift": (E, F), "wkv": (E, hidden, hidden)}``.
    """
    del n_envs
    D = hidden

    def init():
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        return {
            "mu": jax.random.uniform(ks[0], (4, n_features)),  # r/k/v/w mixes
            "w_r": jax.random.normal(ks[1], (n_features, D))
            / jnp.sqrt(n_features),
            "w_k": jax.random.normal(ks[2], (n_features, D))
            / jnp.sqrt(n_features),
            "w_v": jax.random.normal(ks[3], (n_features, D))
            / jnp.sqrt(n_features),
            "w_decay": jax.random.normal(ks[4], (n_features, D))
            / jnp.sqrt(n_features),
            "decay_base": jnp.zeros((D,)),
            "bonus": jnp.zeros((D,)),
            "w_o": jax.random.normal(ks[5], (D, n_actions)) / jnp.sqrt(D),
        }
    params = host_params(init)

    def apply_carry(params, feats, carry):
        shift, S = carry["shift"], carry["wkv"]          # (E,F), (E,D,D)
        mixed = feats[None] + params["mu"][:, None, :] * (shift - feats)[None]
        r = _rowdot(mixed[0], params["w_r"])             # (E, D)
        k = _rowdot(mixed[1], params["w_k"])
        v = _rowdot(mixed[2], params["w_v"])
        lw = _rowdot(mixed[3], params["w_decay"]) + params["decay_base"][None]
        log_w = jnp.clip(-jnp.exp(jnp.clip(lw, -8.0, 3.0)), -20.0, -1e-5)
        kv = k[..., :, None] * v[..., None, :]           # (E, D, D)
        att = S + params["bonus"][None, :, None] * kv
        out = (r[..., :, None] * att).sum(-2)            # einsum('ek,ekv->ev')
        S_new = jnp.exp(log_w)[..., :, None] * S + kv
        actions = _scale(_rowdot(out, params["w_o"]), low, high)
        return actions, {"shift": feats, "wkv": S_new}

    def init_carry(n_envs):
        return {"shift": jnp.zeros((n_envs, n_features), jnp.float32),
                "wkv": jnp.zeros((n_envs, D, D), jnp.float32)}

    return ModelAdapter(None, "rwkv6_policy", params=params,
                        apply_carry=apply_carry, init_carry=init_carry)


POLICIES = {
    "linear": linear_builder,
    "mlp": mlp_builder,
    "rglru": rglru_builder,
    "rwkv6": rwkv6_builder,
}


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    """Registry spec: a policy name plus builder kwargs.

    ``PolicyConfig("rglru", {"hidden": 32, "use_pallas": True})`` resolves
    through :func:`build_policy`; unknown names raise ``KeyError`` naming
    the registered set.
    """
    name: str
    kwargs: Mapping[str, Any] = dataclasses.field(default_factory=dict)


def build_policy(spec, n_features: int, n_actions: int, n_envs: int, *,
                 certify: bool = True, **overrides) -> ModelAdapter:
    """Resolve a registry name / :class:`PolicyConfig` to a certified
    :class:`~repro.runtime.predictor.ModelAdapter`.

    Certification runs BEFORE the adapter is built for the system's real
    shapes, at small-E probes with the real feature/action counts (plus
    the two-env-count param-replication probe), and raises
    :class:`~repro.analysis.contracts.ContractViolation` naming rule,
    primitive and source on a bad builder. The resulting certificate is
    attached as ``adapter.certificate`` — the fused/sharded system modes
    demand it at construction — and cached by
    ``(name, kwargs, probe shapes)`` so repeated standups skip re-tracing.
    """
    if isinstance(spec, str):
        spec = PolicyConfig(spec)
    try:
        builder = POLICIES[spec.name]
    except KeyError:
        raise KeyError(
            f"Unrecognized policy provided: {spec.name!r} "
            f"(registered: {sorted(POLICIES)})") from None
    kwargs = dict(spec.kwargs)
    kwargs.update(overrides)
    bound = functools.partial(builder, **kwargs) if kwargs else builder
    cert = None
    if certify:
        probes = ((4, n_features, n_actions),)
        key = (spec.name, tuple(sorted(kwargs.items())), probes)
        cert = certify_policy(bound, probes, name=spec.name, cache_key=key)
    adapter = bound(n_features, n_actions, n_envs=n_envs)
    adapter.certificate = cert
    return adapter
