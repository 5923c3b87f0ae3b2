"""Logical-dim -> mesh-axis resolution with divisibility fallback.

Every tensor in the system (params, optimizer state, activations, caches,
batches) carries a tuple of *logical dim names* (see models/param.py). This
module maps those names onto mesh axes through an ordered candidate list:
the first candidate whose axis product divides the dim size — and whose axes
are still unused in this tensor — wins; otherwise the dim is replicated.

That one mechanism covers all ten architectures: head counts in
{8, 10, 16, 24, 32, 48, 56} (kv-head sharding when it divides, head_dim
sharding otherwise — interleaved RoPE keeps that shard-local), a vocab of
92553 that refuses to divide 16 (falls back to d_model), 64- and 16-expert
MoEs, ring caches, recurrent states.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import ShardingConfig


ENV_AXIS = "data"  # scan-engine mesh axis name: envs -> data parallelism


def env_mesh(n_envs: int, devices=None, axis_name: str = ENV_AXIS) -> Mesh:
    """One-axis device mesh for the env-sharded scan engine.

    The (K, E, S, M) scan batch is data-parallel over E (per-env state rows
    never interact), so the mesh is a single ``data`` axis over the host's
    devices. The "never interact" part is a checkable contract: everything
    dispatched on this mesh must be per-env row-wise, with dots phrased so
    rounding is independent of rows-per-device (``linear_policy``'s
    multiply+reduce) — ``repro.analysis`` enforces it on the decision path
    at system construction by jaxpr provenance (ROADMAP.md "Invariant
    catalog"). Uses the largest device count that divides ``n_envs`` — on a
    lone CPU device this degenerates to a 1-device mesh and ``shard_map``
    becomes a no-op partitioning, which is what lets the sharded mode run
    (and be tested) everywhere. Multi-device CPU recipe:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, set before JAX
    initializes (``benchmarks/run.py --host-devices 8`` does this).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    while n > 1 and n_envs % n:
        n -= 1
    return compat.make_mesh(np.asarray(devices[:n]), (axis_name,))


def env_specs(shape_tree, env_axis: int, axis_name: str = ENV_AXIS):
    """PartitionSpec pytree sharding dim ``env_axis`` of every array leaf.

    Leaves with too few dims to carry an env axis are replicated — that
    one rank rule places every carry in the system: the pipeline state's
    scalar ``tick_index``, and the fused decision carry's ``have_prev`` /
    ``tick`` / replay-ring ``cursor`` scalars all replicate while the
    per-env rows (state leaves, prev obs/actions, (E, C, ...) replay
    storage) split on the env dim. Used by
    ``core.pipeline.make_run_many_sharded`` and
    ``make_run_many_decide_sharded`` for the carries (env_axis=0) and the
    K-leading scan batch / stacked outputs (env_axis=1).
    """
    def one(s):
        if s.ndim <= env_axis:
            return P()
        spec = [None] * s.ndim
        spec[env_axis] = axis_name
        return P(*spec)

    return jax.tree.map(one, shape_tree,
                        is_leaf=lambda x: hasattr(x, "ndim"))


def place_env_tree(tree, env_axis: int, mesh: Mesh,
                   axis_name: str = ENV_AXIS, specs=None):
    """Device-put a pytree onto the env mesh with :func:`env_specs` layout.

    The elastic regrow path uses this after ``elastic.grow_env_tree``: the
    grown host-side state / decide-carry / replay trees are re-placed on
    the (possibly re-chosen) env mesh before the rebuilt pipeline's first
    dispatch, so surviving rows land on their new owner devices without a
    layout-change inside jit. Scalars (rank <= env_axis) replicate, per the
    same rank rule that places the carries. ``specs`` overrides the spec
    tree — the decide carry passes :func:`decide_specs` so policy weights
    replicate instead of rank-rule sharding."""
    if specs is None:
        specs = env_specs(tree, env_axis, axis_name)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: hasattr(x, "ndim"))


def decide_specs(dstate_tree, env_axis: int, axis_name: str = ENV_AXIS):
    """:func:`env_specs` for the fused decision carry, with the ``policy``
    params subtree forced to replicate.

    Policy weights are batch-global — a (F, A) weight has no env dim — but
    the rank rule of :func:`env_specs` can't know that: any weight whose
    leading dim happened to divide E would silently shard on the feature
    dim and each device would run a different slice of the policy. The
    carry travels as a ``DecideState`` NamedTuple, so the policy subtree's
    specs are replaced wholesale with replicated ``P()``.

    The model's recurrent carry (``DecideState.carry``, PR 8) is NOT
    special-cased: its leaves are per-env ``(E, ...)`` by the certified
    registry contract (``analysis/certify.py``'s carry structural check),
    so the plain rank rule shards them on dim 0 like every other env
    buffer — and ``certify_policy``'s ``param-replication`` probe is what
    guarantees per-env state never hides in the replicated params subtree
    instead.
    """
    specs = env_specs(dstate_tree, env_axis, axis_name)
    rep = jax.tree.map(lambda _: P(), dstate_tree.policy,
                       is_leaf=lambda x: hasattr(x, "ndim"))
    return specs._replace(policy=rep)


def make_abstract_mesh(mesh_shape) -> "jax.sharding.AbstractMesh":
    """Planner-only mesh from ``((name, size), ...)`` — no devices needed.

    Routes through ``repro.compat`` because ``AbstractMesh``'s constructor
    signature is a version seam of JAX's API; every
    NamedSharding the planner emits is mesh-shape-only, so an abstract mesh
    is enough to unit-test resolution against a 256-chip topology.
    """
    names = tuple(n for n, _ in mesh_shape)
    sizes = tuple(s for _, s in mesh_shape)
    return compat.abstract_mesh(sizes, names)

# Data-parallel submesh: prefer pod+data, fall back to data alone.
DP = [("pod", "data"), ("data",)]
MODEL = [("model",)]

# ---------------------------------------------------------------------------
# Rule tables. Order inside each list = preference order.
# ---------------------------------------------------------------------------

def param_rules(perf: ShardingConfig) -> dict:
    rules = {
        "vocab": [("model",)],
        "d_ff": [("model",)],
        "experts": [("model",)],
        "heads_flat": [("model",)],   # H*Dh — divides 16 for every arch
        "kv_flat": [("model",)],      # Hkv*Dh — ditto
        "lru_width": [("model",)],
        # d_model only shards when nothing narrower could (embed fallback)
        "d_model": [("model",)],
    }
    if perf.embed_shard == "d_model":
        # force embedding tables onto d_model (hillclimb lever): handled by
        # resolve() because 'vocab' is removed so d_model picks up 'model'.
        rules = dict(rules)
        rules["vocab"] = []
    return rules


def act_rules(perf: ShardingConfig, *, seq_parallel: Optional[bool] = None) -> dict:
    sp = perf.seq_parallel if seq_parallel is None else seq_parallel
    rules = {
        "batch": list(DP),
        "envs": list(DP),
    }
    if sp:
        rules["seq"] = [("model",)]
    return rules


def cache_rules(perf: ShardingConfig) -> dict:
    rules = {
        "batch": list(DP),
        "kv_heads": [("model",)],
        "lru_width": [("model",)],
        "heads_flat": [("model",)],
        "d_model": [],
        "rwkv_heads": [("model",)],
        # spread the 32k/500k KV cache over 'model' (flash-decode style):
        # decode contracts over cache_seq, giving a small per-step psum
        "cache_seq": [("model",)] if perf.shard_cache_seq else [],
    }
    return rules


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

def _axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


# Dims earlier in this list get first pick of mesh axes. d_model is LAST on
# purpose: it is the fallback (e.g. the 92553-vocab embed table) and must not
# steal 'model' from d_ff/heads_flat just because it is dim 0 of every weight.
PRIORITY = ("experts", "vocab", "d_ff", "heads_flat", "kv_flat", "lru_width",
            "cache_seq", "kv_heads", "rwkv_heads", "batch", "envs", "seq",
            "heads", "head_dim", "d_model")


def resolve(shape: Sequence[int], dims: Sequence[str], mesh: Mesh,
            rules: dict) -> NamedSharding:
    assert len(shape) == len(dims), (shape, dims)
    order = sorted(range(len(dims)),
                   key=lambda i: (PRIORITY.index(dims[i])
                                  if dims[i] in PRIORITY else len(PRIORITY)))
    spec = [None] * len(dims)
    used: set = set()
    for i in order:
        size, dim = shape[i], dims[i]
        for cand in rules.get(dim, []):
            axes = tuple(a for a in cand if a in mesh.axis_names)
            if not axes or any(a in used for a in axes):
                continue
            if size % _axes_size(mesh, axes) == 0:
                spec[i] = axes if len(axes) > 1 else axes[0]
                used.update(axes)
                break
    return NamedSharding(mesh, P(*spec))


def tree_shardings(spec_tree, dims_tree, mesh: Mesh, rules: dict):
    """specs: ShapeDtypeStruct pytree; dims: matching logical-dims pytree."""
    return jax.tree.map(
        lambda s, d: resolve(s.shape, d, mesh, rules),
        spec_tree, dims_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def zero1_shardings(spec_tree, dims_tree, mesh: Mesh, perf: ShardingConfig):
    """Optimizer-state shardings: param sharding + extra 'data' shard.

    For every leaf, first resolve the param rules, then give the first dim
    that is still replicated AND divisible by the data-axis size to 'data'
    (and 'pod' too when it also divides). This is ZeRO-1: m/v (and the
    fp32 view of the update) are partitioned across data-parallel peers.
    """
    rules = param_rules(perf)
    if not perf.zero1:
        return tree_shardings(spec_tree, dims_tree, mesh, rules)

    def one(s, d):
        base = resolve(s.shape, d, mesh, rules)
        parts = list(base.spec) + [None] * (len(s.shape) - len(base.spec))
        used = set()
        for p in parts:
            if p is None:
                continue
            used.update(p if isinstance(p, tuple) else (p,))
        for dp_axes in DP:
            axes = tuple(a for a in dp_axes if a in mesh.axis_names)
            if not axes or any(a in used for a in axes):
                continue
            n = _axes_size(mesh, axes)
            for i, (size, part) in enumerate(zip(s.shape, parts)):
                # never shard the scan ('layers') dim: per-iteration
                # dynamic-slice/update of a layers-sharded stack forces GSPMD
                # to materialize the whole (unsharded!) grad stack in-loop
                if d[i] == "layers":
                    continue
                if part is None and size % n == 0:
                    parts[i] = axes if len(axes) > 1 else axes[0]
                    return NamedSharding(mesh, P(*parts))
        return base

    return jax.tree.map(one, spec_tree, dims_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def strip_leading_dim(sh: NamedSharding, mesh: Mesh) -> NamedSharding:
    """Sharding for a per-layer slice of a layer-stacked param."""
    parts = list(sh.spec)
    if parts:
        parts = parts[1:]
    return NamedSharding(mesh, P(*parts))


def gather_hook(mesh: Mesh, perf: ShardingConfig, dims_subtree):
    """ZeRO-3: constrain a scanned group's param slices to their compute
    (model-axis-only) sharding; storage keeps the extra 'data' shard. The
    constraint's transpose reduce-scatters the grads back — ZeRO gradient
    semantics fall out of GSPMD for free."""
    rules = param_rules(perf)

    def hook(group_params):
        def one(x, d):
            # d includes the leading 'layers' dim of the stacked def; the
            # slice inside scan has lost it
            sub = d[1:] if len(d) == x.ndim + 1 else d
            sh = resolve(x.shape, sub, mesh, rules)
            return jax.lax.with_sharding_constraint(x, sh)

        return jax.tree.map(one, group_params, dims_subtree)

    return hook


def batch_sharding(mesh: Mesh, ndim: int, perf: ShardingConfig,
                   *, seq_axis: Optional[int] = None,
                   batch_size: Optional[int] = None) -> NamedSharding:
    """Sharding for a batch array: dim 0 = batch over DP, rest replicated
    (optionally seq over 'model')."""
    for dp_axes in DP:
        axes = tuple(a for a in dp_axes if a in mesh.axis_names)
        if not axes:
            continue
        if batch_size is not None and batch_size % _axes_size(mesh, axes) != 0:
            continue
        spec = [axes if len(axes) > 1 else axes[0]] + [None] * (ndim - 1)
        if seq_axis is not None:
            spec[seq_axis] = "model"
        return NamedSharding(mesh, P(*spec))
    spec = [None] * ndim
    if seq_axis is not None:
        spec[seq_axis] = "model"
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def constrain_batch(x, mesh: Mesh, perf: ShardingConfig):
    """with_sharding_constraint helper used at block boundaries.

    With ``seq_parallel`` the residual stream (B, S, D) is additionally
    sharded over 'model' on S — Megatron-style sequence parallelism: GSPMD
    turns the per-layer psums into reduce-scatter/all-gather pairs and the
    norms/residual adds run seq-sharded.
    """
    seq_axis = None
    if perf.seq_parallel and x.ndim >= 3 and "model" in mesh.axis_names \
            and x.shape[1] % mesh.shape["model"] == 0:
        seq_axis = 1
    sh = batch_sharding(mesh, x.ndim, perf, batch_size=x.shape[0],
                        seq_axis=seq_axis)
    return jax.lax.with_sharding_constraint(x, sh)


def attn_constrainers(mesh: Mesh, perf: ShardingConfig) -> dict:
    """Constraint hooks for the two attention sharding modes.

    "heads": tensors shaped (B, S, H, ...) -> batch over DP, dim 2 over
             'model' (requires H % model == 0 — checked by the caller).
    "qs":    tensors shaped (B, nq, ...)  -> batch over DP, dim 1 over
             'model' (context-parallel q chunks).
    """
    msize = mesh.shape.get("model", 1)

    def _dp(batch_size):
        for dp_axes in DP:
            axes = tuple(a for a in dp_axes if a in mesh.axis_names)
            if axes and batch_size % _axes_size(mesh, axes) == 0:
                return axes if len(axes) > 1 else axes[0]
        return None

    def c_heads(x):
        if msize <= 1 or x.shape[2] % msize != 0:
            return constrain_batch(x, mesh, perf)
        spec = [_dp(x.shape[0]), None, "model"] + [None] * (x.ndim - 3)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))

    def c_qs(x):
        if msize <= 1 or x.shape[1] % msize != 0:
            return constrain_batch(x, mesh, perf)
        spec = [_dp(x.shape[0]), "model"] + [None] * (x.ndim - 2)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))

    def c_ff(x):
        # keep d_ff-wide activations sharded through the pointwise ops so the
        # backward pass never materializes (B, S, d_ff) unsharded
        if msize <= 1 or x.shape[-1] % msize != 0:
            return x
        spec = [_dp(x.shape[0])] + [None] * (x.ndim - 2) + ["model"]
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))

    return {"heads": c_heads, "qs": c_qs, "ff": c_ff}
