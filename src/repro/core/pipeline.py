"""PerceptaPipeline — the per-tick program: Figure 1 as one tensor program.

Three execution modes (the measured §Perf axis on CPU, same math):
  * ``modular`` — paper-faithful: each module (harmonize, anomaly, gap-fill,
    normalize, aggregate, encode) is its own jitted call with host hops in
    between, exactly the RabbitMQ-separated component chain the paper draws.
  * ``fused``   — the whole tick is ONE jit (and batched across all
    environments), which is the TPU-native re-think: no host hops, XLA fuses
    across module boundaries, one dispatch per tick.
  * ``scan``    — ``run_many``: K pre-batched windows execute as a single
    ``jax.lax.scan`` over the tick function. The state pytree never leaves
    the device between windows (and is donated into the call), so the
    Manager pays ONE Python dispatch per K windows instead of one per
    window — the amortization that makes small-E edge deployments fast.
  * ``scan_sharded`` — the same K-window scan executed under ``shard_map``
    on a one-axis device mesh with the env dimension sharded (envs -> the
    ``data`` axis; see ``distribution.sharding.env_mesh``). Every per-env
    row of the batch, the state pytree, and the stacked outputs lives on
    exactly one device; the math is collective-free, so outputs are
    bit-identical to ``scan``. On a single device the mesh degenerates and
    the mode equals ``scan``; on an N-device pod it runs K windows x E envs
    with E/N env rows per chip. CPU testing recipe:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (must be set
    before JAX initializes; ``benchmarks/run.py --host-devices 8``).
  * ``scan_fused_decide`` — ``run_many_decide``: the SAME K-window scan
    with the decision path fused into the scan body. Each window's
    FeatureFrame flows directly into an injected per-window ``decide``
    step (policy gemm, action validation, reward terms, replay-ring
    write) without ever leaving the device, and the scan carry becomes
    ``(PipelineState, decide carry)`` — one donated pytree, one device
    dispatch per K windows for the WHOLE loop, ingest to banked
    transition. Host transfer shrinks from the stacked (K, E, F) features
    + raw + (K, E, S, T) frames to the small per-window outputs
    (:class:`DecideBatch`: actions, rewards, violation flags and per-env
    observed/filled/anomalous COUNTS — host metrics divide the exact
    integer counts, so the fractions match the reference bit for bit).
    ``scan_fused_decide_sharded`` runs it under ``shard_map`` on the env
    mesh: the decide carry shards on the env dim exactly like the
    pipeline state (scalars — have_prev, tick, ring cursor — replicated),
    closed-over policy weights are replicated, and the decision math is
    per-env row-wise (reward custom fns must be row-wise too), so no
    collectives and bit-identity with the unsharded engine hold just like
    ``scan_sharded``.

All mesh/shard_map spellings route through ``repro.compat`` (JAX support
matrix in ROADMAP.md).

State is a single pytree carried tick-to-tick (gap-fill memory, anomaly
stats, normalizer stats) — checkpointable alongside model params.

Time convention (long-horizon float32 safety): device-visible timestamps
are WINDOW-RELATIVE offsets. The host (``Accumulator.close_windows(...,
rebase=True)``) subtracts each window's start from the raw sample
timestamps in float64 *before* the float32 cast, and the system passes
``window_start = 0`` for every window — so sub-second deltas stay exact no
matter how far the absolute stream clock has advanced (absolute float32
seconds quantize to >=1s past t~2^24). Two pieces of absolute time survive:

  * the seasonal tick-of-day slot is computed with exact integer arithmetic
    from ``state.tick_index`` and the static ``PipelineConfig.tick0``
    offset (windows are consecutive by construction, so the absolute tick
    position is ``tick0 + tick_index * n_ticks``);
  * the ``prev_value``/``prev_ts`` carry is stored in the frame of the
    window that produced it, and each tick re-expresses it in the current
    window's frame by subtracting one window length (again: consecutive
    windows by construction).

Callers that drive ``tick``/``run_many`` directly may still pass absolute
starts with absolute raw timestamps — every in-window comparison is
shift-invariant — but the ``interp_streams`` cross-window bridge and the
seasonal slots assume the consecutive-window convention above.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import aggregate as agg
from repro.core import anomaly as an
from repro.core import f32
from repro.core import gapfill as gf
from repro.core import harmonize as hz
from repro.core import normalize as nz
from repro.core.frame import FeatureFrame, RawWindow, TickFrame


class PipelineState(NamedTuple):
    gapfill: gf.GapFillState
    anomaly: an.AnomalyState
    norm: nz.NormState
    prev_value: jax.Array   # (E, S) carry for cross-window interpolation
    prev_ts: jax.Array
    tick_index: jax.Array   # () int64-ish step counter


@dataclass(frozen=True)
class PipelineConfig:
    n_envs: int
    n_streams: int
    n_ticks: int = 16            # ticks per window
    tick_s: float = 60.0         # model time resolution (e.g. 1 min)
    max_samples: int = 64        # raw samples per stream per window (padded)
    agg: str = "mean"            # harmonization aggregation
    harmonize_method: str = "segment"  # segment (O(M)) | onehot (O(M*T))
    interp_streams: bool = False # use interpolating harmonizer instead
    gap_strategy: str = "locf"   # locf | linear | ewma | seasonal
    anomaly_policy: str = "clip" # clip | mean | missing
    k_sigma: float = 6.0
    seasonal_slots: int = 24
    # cross-stream relationships: rows of (F, S) — defaults to identity
    combine_weights: Optional[tuple] = None
    per_tick_features: bool = False
    # how features summarize the tick dim: "last" keeps the final tick
    # (the original behaviour, exact); any other AGGS name routes through
    # aggregate.window_agg — the paper's Manager "sums, averages" logic
    feature_agg: str = "last"
    # route the locf gap-fill stage and the feature_agg window stats
    # through the Pallas kernels in repro.kernels.{locf,window_agg}
    # (interpret mode off-TPU); False keeps the pure-XLA paths
    use_pallas: bool = False
    # absolute tick position of the stream origin (round(t0 / tick_s)):
    # seasonal tick-of-day slots are computed exactly as
    # (tick0 + tick_index * n_ticks + tick) mod seasonal_slots, so they
    # survive window-relative timestamps and arbitrarily long horizons
    tick0: int = 0

    def weights(self):
        if self.combine_weights is None:
            return jnp.eye(self.n_streams, dtype=jnp.float32)
        return jnp.asarray(self.combine_weights, jnp.float32)

    @property
    def n_features(self):
        w = self.combine_weights
        n = self.n_streams if w is None else len(w)
        return n * (self.n_ticks if self.per_tick_features else 1)


def init_state(cfg: PipelineConfig) -> PipelineState:
    E, S = cfg.n_envs, cfg.n_streams
    return PipelineState(
        gapfill=gf.init_state(E, S, cfg.seasonal_slots),
        anomaly=an.init_state(E, S),
        norm=nz.init_state(E, S),
        prev_value=jnp.zeros((E, S), jnp.float32),
        prev_ts=jnp.full((E, S), -1e30, jnp.float32),
        tick_index=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Stage functions (shared by both modes)
# ---------------------------------------------------------------------------

def stage_harmonize(cfg: PipelineConfig, state, raw: RawWindow, window_start):
    ticks = hz.tick_grid(window_start, cfg.tick_s, cfg.n_ticks)
    if cfg.interp_streams:
        # the carry is stored in the PREVIOUS window's time frame; windows
        # are consecutive, so one window length re-expresses it here
        v, obs = hz.harmonize_interp(
            raw, ticks, prev_value=state.prev_value,
            prev_ts=state.prev_ts - cfg.n_ticks * cfg.tick_s)
    elif cfg.harmonize_method == "segment":
        v, obs = hz.harmonize_segment(raw, ticks, cfg.tick_s, cfg.agg)
    else:
        v, obs = hz.harmonize(raw, ticks, cfg.tick_s, cfg.agg)
    return v, obs, ticks


def stage_anomaly(cfg: PipelineConfig, state, v, obs):
    spikes = an.detect_zscore(v, obs, state.anomaly, cfg.k_sigma)
    v, obs, replaced = an.replace(v, obs, spikes, state.anomaly,
                                  cfg.anomaly_policy, cfg.k_sigma)
    new_anom = an.update_state(state.anomaly, v, obs)
    return v, obs, replaced, new_anom


def stage_gapfill(cfg: PipelineConfig, state, v, obs, ticks):
    # Exact integer tick-of-day. The float form mod((ticks/tick_s), slots)
    # quantizes once absolute float32 ticks pass ~2^24 s and loses the
    # absolute phase entirely under window-relative timestamps. Windows are
    # consecutive, so tick t of the current window sits at absolute tick
    # position tick0 + tick_index*n_ticks + 1 + t; every term is reduced
    # mod seasonal_slots before the multiply so int32 stays exact on any
    # horizon.
    E, T = v.shape[0], v.shape[-1]
    slots = cfg.seasonal_slots
    base = (cfg.tick0 % slots
            + (state.tick_index % slots) * (cfg.n_ticks % slots))
    tod = jnp.mod(base + 1 + jnp.arange(T, dtype=jnp.int32), slots)
    tod = jnp.broadcast_to(tod[None, :], (E, T))
    return gf.gap_fill(v, obs, state.gapfill, ticks, cfg.gap_strategy,
                       tick_of_day=tod, use_pallas=cfg.use_pallas)


def stage_normalize(cfg: PipelineConfig, state, v, obs):
    new_norm = nz.update(state.norm, v, obs)
    return nz.znorm(new_norm, v), new_norm


def stage_features(cfg: PipelineConfig, v_norm, v_raw, obs, filled, ticks):
    mask = obs | filled
    feats = agg.feature_vector(v_norm, mask, cfg.weights(),
                               per_tick=cfg.per_tick_features,
                               feature_agg=cfg.feature_agg,
                               use_pallas=cfg.use_pallas)
    raw = agg.feature_vector(v_raw, mask, cfg.weights(),
                             per_tick=cfg.per_tick_features,
                             feature_agg=cfg.feature_agg,
                             use_pallas=cfg.use_pallas)
    quality = obs.astype(jnp.float32).mean(axis=(1, 2))
    return FeatureFrame(feats, raw, quality, ticks[:, -1])


# ---------------------------------------------------------------------------
# Fused tick
# ---------------------------------------------------------------------------

def tick(cfg: PipelineConfig, state: PipelineState, raw: RawWindow,
         window_start):
    """One full Percepta tick. Returns (new_state, FeatureFrame, TickFrame)."""
    v, obs, ticks = stage_harmonize(cfg, state, raw, window_start)
    v, obs, replaced, new_anom = stage_anomaly(cfg, state, v, obs)
    v, filled, new_gap = stage_gapfill(cfg, state, v, obs, ticks)
    v_norm, new_norm = stage_normalize(cfg, state, v, obs | filled)
    features = stage_features(cfg, v_norm, v, obs, filled, ticks)

    big = jnp.float32(3.4e38)
    ts_b = jnp.where(raw.valid, raw.timestamps, -big).reshape(raw.values.shape)
    last_ts = ts_b.max(-1)
    has = last_ts > -big
    is_last = (ts_b == last_ts[..., None]) & raw.valid
    last_v = f32.einsum("esm,esm->es", raw.values, is_last.astype(jnp.float32)) \
        / jnp.maximum(is_last.sum(-1), 1)
    new_state = PipelineState(
        gapfill=new_gap, anomaly=new_anom, norm=new_norm,
        prev_value=jnp.where(has, last_v, state.prev_value),
        # no observation this window: re-express the old carry in this
        # window's frame so it keeps receding one window length per tick
        prev_ts=jnp.where(has, last_ts,
                          state.prev_ts - cfg.n_ticks * cfg.tick_s),
        tick_index=state.tick_index + 1,
    )
    frame = TickFrame(v, obs, filled, replaced)
    return new_state, features, frame


def mask_env_rows(tree, active):
    """Zero every env row of ``tree``'s leaves where ``active`` is False.

    The elastic engine's ONLY sanctioned way of combining the slot mask
    with data: a ``select`` per leaf (broadcast over trailing dims). Active
    rows pass through untouched — ``where(True, x, 0) == x`` bit for bit —
    and inactive rows become deterministic zeros of the leaf dtype (the
    select also kills any NaN/Inf garbage a cold slot computed). Never
    compact, sort, or index by the mask; the ``env-mask-gate`` contract
    rule rejects that shape (rows would cross shards under the env mesh).

    The selects are fenced by ``lax.optimization_barrier`` on BOTH sides:
    XLA otherwise fuses them into the producing computation's epilogue
    (or into a downstream consumer's kernel — in the fused decide scan
    the masked raw feeds the reward reduction in the same body), and the
    changed fusion shape can re-contract multiply-add chains (1-ulp
    drift vs the dense build — observed on the reward reduction on
    XLA:CPU). The fences pin the surrounding math to compile exactly as
    it does without the mask, which is what makes "active rows
    bit-identical to a dense system over the same envs" hold, not just
    "close".
    """
    tree = jax.lax.optimization_barrier(tree)

    def leaf(x):
        m = active.reshape((active.shape[0],) + (1,) * (jnp.ndim(x) - 1))
        return jnp.where(m, x, jnp.zeros((), jnp.asarray(x).dtype))
    return jax.lax.optimization_barrier(jax.tree.map(leaf, tree))


def run_many(cfg: PipelineConfig, state: PipelineState, raws: RawWindow,
             window_starts, active=None):
    """K windows as ONE ``lax.scan`` over :func:`tick`.

    ``raws`` is a RawWindow whose leaves carry a leading K axis
    (K, E, S, M); ``window_starts`` is (K, E). Returns
    ``(final_state, FeatureFrame, TickFrame)`` with the frame leaves stacked
    along a leading K axis — window k's outputs are exactly what K
    sequential ``tick`` calls would have produced (same math, same order).

    ``active`` (E,) bool is the elastic slot mask: a traced input (attach/
    detach between batches never retraces), masking the stacked per-window
    outputs to garbage-free zeros on inactive rows. State updates need no
    gating — the host feeds inactive slots all-invalid raw windows, under
    which every stage's update is a natural no-op — so active-row outputs
    and the carried state stay bit-identical to the dense engine.
    """
    def body(carry, xs):
        raw, ws = xs
        new_state, feats, frame = tick(cfg, carry, raw, ws)
        if active is not None:
            feats = mask_env_rows(feats, active)
            frame = mask_env_rows(frame, active)
        return new_state, (feats, frame)

    final_state, (feats, frames) = jax.lax.scan(body, state,
                                                (raws, window_starts))
    return final_state, feats, frames


class DecideBatch(NamedTuple):
    """Per-window outputs of the fused decision scan (leading K axis).

    Everything the Manager's host loop needs, and nothing bigger: the
    decision outputs are (K, E[, A]) and the pipeline-quality metrics are
    exact per-env int32 COUNTS over the (S, T) tick grid — the host
    divides them in float64, reproducing ``np.mean`` over the full frame
    bit for bit without transferring the (K, E, S, T) frame stack.
    ``features`` stays on device unless a host sink (LogDB) actually
    fetches it — JAX only pays the device->host copy per leaf touched.
    """
    actions: jax.Array      # (K, E, A) validated actions
    rewards: jax.Array      # (K, E)
    per_term: jax.Array     # (K, E, n_terms)
    violated: jax.Array     # (K, E) bool — pre-clamp envelope violations
    features: jax.Array     # (K, E, F) — fetched only when a sink needs it
    observed: jax.Array     # (K, E) int32 counts over (S, T)
    filled: jax.Array       # (K, E) int32
    anomalous: jax.Array    # (K, E) int32


def run_many_decide(cfg: PipelineConfig, decide, state: PipelineState,
                    dstate, raws: RawWindow, window_starts):
    """K windows + K decisions as ONE ``lax.scan``: :func:`run_many` with
    the decision path fused into the scan body.

    ``decide`` is a ``(step, bank)`` pair (see
    ``runtime.predictor.DecideFns``): ``step`` runs one window's policy/
    validation/reward math inside the scan — exactly the per-window (E, F)
    computation of the reference ``on_tick`` step, so outputs stay
    bit-identical to the two-dispatch path — and emits that window's
    replay transition row; ``bank`` then writes all K stacked rows AFTER
    the scan in one exact ring scatter. Only the small prev/tick part of
    the decide carry rides the scan (the (E, C, F) replay storage through
    a scan carry measured a full copy per dispatch — as a plain donated
    input updated by one scatter, XLA aliases it in place). Returns
    ``(final_state, final_dcarry, DecideBatch)``.

    Elastic slot pools ride the decide carry: when ``dstate.active`` is
    set (an (E,) bool carry leaf — membership changes between batches
    re-dispatch with new mask VALUES, no retrace), the per-window pipeline
    outputs are masked to zeros on inactive rows (the decide step masks
    its own outputs — see ``runtime.predictor.make_decide_fn``), and the
    post-scan bank marks ring rows valid per env: window 0's transition
    closes a pair begun LAST batch, so it is valid only for envs with
    ``prev_ok & active`` (a slot attached this batch has no previous
    window; ``prev_ok`` is the per-env twin of the scalar ``have_prev``
    chain), later windows for every active env. The scalar cursor chain —
    and therefore ring positions — stays exactly the dense engine's.
    """
    step, bank = decide
    elastic = getattr(dstate, "active", None) is not None

    def body(carry, xs):
        pstate, dcarry = carry
        raw, ws = xs
        new_state, feats, frame = tick(cfg, pstate, raw, ws)
        if elastic:
            feats = mask_env_rows(feats, dcarry.active)
            frame = mask_env_rows(frame, dcarry.active)
        new_dcarry, (actions, reward, per_term, violated), trans = step(
            dcarry, feats)
        out = DecideBatch(
            actions=actions, rewards=reward, per_term=per_term,
            violated=violated, features=feats.features,
            # exact per-env counts (S*T <= int32 by construction); the
            # cross-env total is summed host-side so the sharded engine
            # stays collective-free
            observed=jnp.sum(frame.observed, axis=(1, 2), dtype=jnp.int32),
            filled=jnp.sum(frame.filled, axis=(1, 2), dtype=jnp.int32),
            anomalous=jnp.sum(frame.anomalous, axis=(1, 2), dtype=jnp.int32))
        return (new_state, new_dcarry), (out, trans)

    # the ring stays OUT of the scan carry: thread the small decide state,
    # then bank the stacked transitions with one scatter
    small = dstate._replace(replay=None)
    (final_state, final_small), (outs, trans) = jax.lax.scan(
        body, (state, small), (raws, window_starts))
    if elastic:
        K = jnp.shape(window_starts)[0]
        E = dstate.active.shape[0]
        rows = jnp.broadcast_to(dstate.active[None, :], (K, E))
        row0 = (dstate.active & dstate.prev_ok)[None, :]
        env_mask = jnp.concatenate([row0, rows[1:]], axis=0)
        final_dcarry = final_small._replace(
            replay=bank(dstate.replay, trans, env_mask=env_mask),
            prev_ok=dstate.prev_ok | dstate.active)
    else:
        final_dcarry = final_small._replace(replay=bank(dstate.replay, trans))
    return final_state, final_dcarry, outs


def _decide_program(cfg: PipelineConfig, decide):
    """``run_many_decide`` bound to ``cfg`` and ``decide``, under its own
    name: jit names the compiled program after ``__name__``
    (``jit_run_many_decide`` in a profiler trace), where a bare
    ``functools.partial`` has none and reaches the trace as
    ``jit__unknown``."""
    fn = functools.partial(run_many_decide, cfg, decide)
    fn.__name__ = "run_many_decide"
    return fn


def make_run_many_decide_sharded(cfg: PipelineConfig, decide, dstate,
                                 mesh=None):
    """Env-sharded fused decision engine: :func:`run_many_decide` under
    ``shard_map`` on the one-axis env mesh.

    The whole fused carry shards on the env dim: pipeline state leaves and
    decide-carry leaves (prev obs/actions rows, replay ring rows) split on
    dim 0, the (K, ...) batch and stacked :class:`DecideBatch` outputs on
    dim 1, and every scalar (``tick_index``, ``have_prev``, the decide
    tick counter, the ring ``cursor``) replicated — ``sharding.env_specs``
    resolves all of that by leaf rank. Policy weights ride the carry's
    ``policy`` subtree (hot-swappable by the online trainer) and are
    explicitly replicated by ``sharding.decide_specs`` — the rank rule
    alone would mis-shard a weight whose leading dim divides E. The
    decision math must be per-env row-wise (builtin reward terms are;
    custom fns must not reduce across envs), which keeps the body
    collective-free and the outputs bit-identical to the unsharded
    engine. ``dstate`` is only a shape/dtype template for spec probing.

    Build-time trace: probing the output specs runs ``jax.eval_shape``
    over the fused body HERE, so the decide step (and any model inside
    it) must be traceable at construction time — a policy closing over
    host state must have that state populated before the system is built
    (``examples/serve_edge.py`` seeds its codec norm snapshot first).
    """
    from repro.distribution import sharding as shard_lib

    if mesh is None:
        mesh = shard_lib.env_mesh(cfg.n_envs)
    fn = _decide_program(cfg, decide)
    E, S, M = cfg.n_envs, cfg.n_streams, cfg.max_samples
    state_s = jax.eval_shape(lambda: init_state(cfg))
    dstate_s = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        dstate)
    raw_s = RawWindow(jax.ShapeDtypeStruct((1, E, S, M), jnp.float32),
                      jax.ShapeDtypeStruct((1, E, S, M), jnp.float32),
                      jax.ShapeDtypeStruct((1, E, S, M), jnp.bool_))
    starts_s = jax.ShapeDtypeStruct((1, E), jnp.float32)
    out_state_s, out_dstate_s, out_batch_s = jax.eval_shape(
        fn, state_s, dstate_s, raw_s, starts_s)
    axis = mesh.axis_names[0]
    in_specs = (shard_lib.env_specs(state_s, 0, axis),
                shard_lib.decide_specs(dstate_s, 0, axis),
                shard_lib.env_specs(raw_s, 1, axis),
                shard_lib.env_specs(starts_s, 1, axis))
    out_specs = (shard_lib.env_specs(out_state_s, 0, axis),
                 shard_lib.decide_specs(out_dstate_s, 0, axis),
                 shard_lib.env_specs(out_batch_s, 1, axis))
    sharded = compat.shard_map(fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs)
    return sharded, mesh


def make_run_many_sharded(cfg: PipelineConfig, mesh=None, elastic=False):
    """Env-sharded scan engine: :func:`run_many` under ``shard_map``.

    Returns ``(fn, mesh)`` where ``fn(state, raws, window_starts)`` has the
    same signature/outputs as :func:`run_many` but executes with the env
    dimension sharded over ``mesh``'s single ``data`` axis: state leaves are
    split on dim 0, the (K, E, S, M) batch / (K, E) starts / stacked outputs
    on dim 1, and the scalar ``tick_index`` is replicated. The tick math is
    per-env (no cross-env reductions anywhere in the stage functions), so
    the body needs no collectives and outputs are bit-identical to
    :func:`run_many`. ``mesh`` defaults to ``sharding.env_mesh(cfg.n_envs)``
    (largest device count dividing E; 1-device meshes degenerate cleanly).

    ``elastic=True`` builds the masked-slot-pool variant, whose ``fn``
    takes a trailing ``active`` (E,) bool argument sharded on the env axis
    like every other per-env row block (each shard masks only its own
    rows; the mask combines by select, so no collectives appear).
    """
    from repro.distribution import sharding as shard_lib

    if mesh is None:
        mesh = shard_lib.env_mesh(cfg.n_envs)
    fn = functools.partial(run_many, cfg)
    # PartitionSpecs depend only on leaf ranks, so probe them with a K=1
    # abstract batch; the jitted wrapper retraces per concrete K as usual.
    E, S, M = cfg.n_envs, cfg.n_streams, cfg.max_samples
    state_s = jax.eval_shape(lambda: init_state(cfg))
    raw_s = RawWindow(jax.ShapeDtypeStruct((1, E, S, M), jnp.float32),
                      jax.ShapeDtypeStruct((1, E, S, M), jnp.float32),
                      jax.ShapeDtypeStruct((1, E, S, M), jnp.bool_))
    starts_s = jax.ShapeDtypeStruct((1, E), jnp.float32)
    probe = (state_s, raw_s, starts_s)
    if elastic:
        probe = probe + (jax.ShapeDtypeStruct((E,), jnp.bool_),)
    out_state_s, out_feats_s, out_frames_s = jax.eval_shape(fn, *probe)
    axis = mesh.axis_names[0]
    in_specs = (shard_lib.env_specs(state_s, 0, axis),
                shard_lib.env_specs(raw_s, 1, axis),
                shard_lib.env_specs(starts_s, 1, axis))
    if elastic:
        in_specs = in_specs + (shard_lib.env_specs(probe[3], 0, axis),)
    out_specs = (shard_lib.env_specs(out_state_s, 0, axis),
                 shard_lib.env_specs(out_feats_s, 1, axis),
                 shard_lib.env_specs(out_frames_s, 1, axis))
    sharded = compat.shard_map(fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs)
    return sharded, mesh


class PerceptaPipeline:
    """User-facing handle; ``mode`` selects scan_sharded/scan/fused/modular.

    ``run_tick`` treats ``scan``/``scan_sharded`` as ``fused`` (single
    windows still take one dispatch); the scan engine is reached through
    :meth:`run_many`, which dispatches to the env-sharded ``shard_map``
    build when ``mode="scan_sharded"`` (``mesh`` overrides the default
    ``distribution.sharding.env_mesh``).
    """

    def __init__(self, cfg: PipelineConfig, mode: str = "fused",
                 donate: bool = False, mesh=None, decide=None,
                 decide_state=None, elastic: bool = False):
        # donate=True requires the caller to treat the passed-in state as
        # consumed (the engine hands back the new state); it is how the
        # scan engine keeps exactly one live state pytree on device. The
        # fused-decide modes donate BOTH carries (pipeline state + decide
        # carry) so the replay ring never gets copied between batches.
        # elastic=True marks the env axis a masked slot pool: the plain
        # scan engines take a trailing (E,) active mask (fused-decide
        # modes carry it inside decide_state instead).
        self.cfg = cfg
        self.mode = mode
        self.donate = donate
        self.elastic = elastic
        tickf = functools.partial(tick, cfg)
        # both paths go through compat.jit_donated: fresh init_state leaves
        # alias their zero buffers, which raw donate_argnums rejects
        self._fused = compat.jit_donated(
            tickf, donate_argnums=(0,) if donate else ())
        donate_scan = (0,) if donate else ()
        if mode in ("scan_fused_decide", "scan_fused_decide_sharded"):
            assert decide is not None and decide_state is not None, \
                "fused-decide modes need decide= and decide_state="
            donate_scan = (0, 1) if donate else ()
            if mode == "scan_fused_decide_sharded":
                scan_fn, self.mesh = make_run_many_decide_sharded(
                    cfg, decide, decide_state, mesh)
            else:
                scan_fn = _decide_program(cfg, decide)
                self.mesh = None
        elif mode == "scan_sharded":
            scan_fn, self.mesh = make_run_many_sharded(cfg, mesh,
                                                       elastic=elastic)
        else:
            scan_fn, self.mesh = functools.partial(run_many, cfg), None
        self._scan = compat.jit_donated(scan_fn, donate_argnums=donate_scan)
        # modular: one jit per module, host transitions in between — the
        # architecture exactly as drawn (baseline for §Perf)
        self._m_harm = jax.jit(functools.partial(stage_harmonize, cfg))
        self._m_anom = jax.jit(functools.partial(stage_anomaly, cfg))
        self._m_gap = jax.jit(functools.partial(stage_gapfill, cfg))
        self._m_norm = jax.jit(functools.partial(stage_normalize, cfg))
        self._m_feat = jax.jit(functools.partial(stage_features, cfg))

    def init_state(self):
        return init_state(self.cfg)

    def run_many(self, state, raws: RawWindow, window_starts, active=None):
        """Scan-fused execution of K pre-batched windows (one dispatch).

        ``active`` (E,) bool is the elastic slot mask (required iff the
        pipeline was built with ``elastic=True``; a traced value, so
        membership changes never retrace)."""
        if self.mode in ("scan_fused_decide", "scan_fused_decide_sharded"):
            raise RuntimeError("fused-decide modes carry a decide state: "
                               "use run_many_decide(state, dstate, ...)")
        if self.elastic:
            assert active is not None, \
                "elastic pipelines need the (E,) active mask per batch"
            return self._scan(state, raws, window_starts, active)
        assert active is None, \
            "active mask passed to a pipeline built with elastic=False"
        return self._scan(state, raws, window_starts)

    def run_many_decide(self, state, dstate, raws: RawWindow, window_starts):
        """Fused pipeline+decision execution of K windows (one dispatch).

        Returns ``(new_state, new_dstate, DecideBatch)``; with
        ``donate=True`` BOTH input carries are consumed."""
        return self._scan(state, dstate, raws, window_starts)

    def run_tick(self, state, raw: RawWindow, window_start):
        if self.mode in ("fused", "scan", "scan_sharded",
                         "scan_fused_decide", "scan_fused_decide_sharded"):
            return self._fused(state, raw, window_start)
        # modular: each stage returns to host before the next is dispatched
        v, obs, ticks = jax.block_until_ready(
            self._m_harm(state, raw, window_start))
        v, obs, replaced, new_anom = jax.block_until_ready(
            self._m_anom(state, v, obs))
        v, filled, new_gap = jax.block_until_ready(
            self._m_gap(state, v, obs, ticks))
        v_norm, new_norm = jax.block_until_ready(
            self._m_norm(state, v, obs | filled))
        features = jax.block_until_ready(
            self._m_feat(v_norm, v, obs, filled, ticks))
        big = jnp.float32(3.4e38)
        ts_b = jnp.where(raw.valid, raw.timestamps, -big)
        last_ts = ts_b.max(-1)
        has = last_ts > -big
        is_last = (ts_b == last_ts[..., None]) & raw.valid
        last_v = f32.einsum("esm,esm->es", raw.values,
                            is_last.astype(jnp.float32)) / \
            jnp.maximum(is_last.sum(-1), 1)
        new_state = PipelineState(
            gapfill=new_gap, anomaly=new_anom, norm=new_norm,
            prev_value=jnp.where(has, last_v, state.prev_value),
            prev_ts=jnp.where(has, last_ts,
                              state.prev_ts
                              - self.cfg.n_ticks * self.cfg.tick_s),
            tick_index=state.tick_index + 1,
        )
        return new_state, features, TickFrame(v, obs, filled, replaced)
