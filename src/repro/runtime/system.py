"""PerceptaSystem — full wiring of Figure 1, multi-environment.

Deployment modes (paper §III.C): the SAME system object serves
  * edge  — one environment, fully local
  * fog   — a few nearby environments
  * cloud — many isolated environments simultaneously
All environments are rows of the batched device pipeline; isolation is by
construction (per-env queues, per-env state rows, per-env model slots).

Time is virtual (``speedup``) so benchmarks can run days of stream time in
seconds. The Manager logic lives in ``run_window``: close each env's window,
assemble the device batch, run the (fused or modular) Percepta tick, run the
Predictor, forward the decisions, log everything.

``mode="scan"`` switches the Manager loop to the scan-fused engine: queues
are drained once per batch, K consecutive windows of every env are closed
into a stacked (K, E, S, M) RawWindow, and ONE device dispatch
(``PerceptaPipeline.run_many``) processes all K windows with the state
carried on device. The decision path is batched the same way: the
Predictor consumes the stacked (K, E, F) features in ONE jitted dispatch
(``Predictor.on_windows`` — policy/validate under ``lax.scan``, K-leading
reward terms, replay appended via the scan-carried ``add_many``), and
Forwarders/DB take per-window batch calls (``dispatch_window`` /
``append_many``, one lock per call). Host-side consumers still see one
result row per window, in window order, bit-identical to the per-window
reference (``batched_consume=False``).

``mode="scan_sharded"`` is the same Manager loop with the device dispatch
executed under ``shard_map`` on an env-sharded mesh (envs -> the ``data``
axis, per-env state rows and batch rows split across devices; see
``core.pipeline.make_run_many_sharded``). Outputs are bit-identical to
``scan``; on one device the mesh degenerates to it. CPU multi-device
recipe: ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before JAX
initializes.

``mode="scan_async"`` (and ``"scan_async_sharded"``, which composes with
the env-sharded dispatch) pipelines host ingest against device compute: a
``runtime.prefetch.WindowPrefetcher`` pump thread assembles window batch
j+1 (clock advance -> receiver poll -> queue drain -> ``close_windows``)
while batch j executes on device via JAX async dispatch, and the Manager
blocks only at result consumption. The pump performs exactly the
clock-advance/poll/drain sequence the synchronous loop would at the same
window boundaries (the deterministic batch-epoch handoff), so outputs are
bit-identical to ``scan`` by construction.

``mode="scan_fused_decide"`` collapses the LAST dispatch boundary: the
Predictor's per-window step (policy gemm, ``validate_actions``, reward
terms, ``replay.add``) is traced INTO the pipeline scan body
(``core.pipeline.run_many_decide``), the decision state
(``predictor.DecideState``: prev obs/actions, have_prev, exact tick
counter, the replay ring) joins the pipeline state in one donated device
carry, and the whole ingest->decide->bank loop costs ONE device dispatch
per K-window batch. Consume only drains host sinks from the small
per-window outputs (actions, rewards, violation flags, exact per-env
observed/filled/anomalous counts); the (K, E, F) feature stack is fetched
only when a LogDB is attached, and the (K, E, S, T) frames never leave
the device. ``"scan_fused_decide_sharded"`` runs the fused scan under
``shard_map`` on the env mesh (decide carry sharded on the env dim,
policy weights replicated, scalars replicated — collective-free, so
bit-identical); ``"scan_fused_decide_async"`` /
``"scan_fused_decide_async_sharded"`` compose with the prefetcher (and,
like all async modes, do not donate). Accessor rules: the replay ring
lives in the donated carry, so read it ONLY through
``system.export_replay(salt)`` / ``snapshot_decide()`` /
``replay_size()`` — never through ``predictor.replay``, which is a stale
snapshot of construction time in these modes.

``scan_k="auto"`` runs ``core.autotune.tune_scan_params`` at construction:
a short measured grid over windows-per-dispatch x env-mesh split picks the
windows/s-optimal configuration for this host/device/shape (result kept on
``self.tuned``).

Device-visible time is WINDOW-RELATIVE (long-horizon float32 safety): the
Accumulator subtracts each window's start in float64 before the float32
cast and every pipeline dispatch receives ``window_start = 0``; absolute
float32 seconds would quantize sub-second deltas past t~2^24 s (~194 days
of stream time — minutes of wall time at high ``speedup``). The seasonal
tick-of-day phase survives via the exact integer ``PipelineConfig.tick0``
offset derived from ``t0``.

``train="online"`` (fused-decide modes only) attaches a
``runtime.trainer.OnlineTrainer``: one jitted sample+AdamW update per
K-window batch, enqueued right AFTER the fused decide dispatch so it runs
in the dispatch bubble while the host consumes. Policy hot-swaps happen
only at batch boundaries (``apply_pending`` swaps the carry's ``policy``/
``version`` leaves before the next dispatch), ``policy_version`` increments
monotonically per applied update, and every replay row / LogDB row is
stamped with the version that produced its action — so each K-batch is
attributable to exactly one policy. With training off (or an idle trainer
on an empty ring) the decide path is bit-identical to the plain fused
modes. Accessors: ``policy_version()``, ``snapshot_policy()``,
``train_stats()``.

``elastic=True`` (scan modes only) turns the env axis into a padded SLOT
POOL: ``env_slots`` rows are allocated up front, an ``active`` (E,) bool
mask — a traced VALUE, so membership changes never retrace — rides every
dispatch (a trailing ``run_many`` input in the plain scan modes, the
``DecideState.active``/``prev_ok`` carry leaves in the fused ones), and
:meth:`attach_env` / :meth:`detach_env` flip slots between window batches
only (the prefetcher's membership epoch tag enforces the boundary in the
async modes). Inactive slots are fed all-invalid raw windows (state
updates are natural no-ops) and masked to deterministic zeros on every
output; they are excluded from decisions, reward/violation stats,
replay banking and sampling (the ring's per-cell ``valid`` column),
LogDB rows and Forwarder traffic — active-row results stay bit-identical
to a dense fixed-E system over the same envs. When the pool fills,
:meth:`resize` grows it (``distribution.elastic``): every env-leading
pytree is padded against a fresh init template, re-placed on the
re-chosen env mesh (sharded modes), and the engine is rebuilt — the one
allowed retrace point; surviving rows resume bit-exactly.

``ingest="columnar"`` (the default) moves record flow onto the
structure-of-arrays fast path: Receivers hand whole polls to
``Translator.translate_batch`` which publishes one ``RecordBatch`` per
(source, env) poll, and the Accumulator buckets them with vectorized
NumPy (argsort/searchsorted) — no Python-level per-record loop anywhere
between the device simulator and the (K, E, S, M) device batch.
``ingest="records"`` keeps the per-payload Record path — the
wire-protocol-faithful baseline the benchmarks compare against. The two
paths produce identical windows for lossless codecs (mqtt json, amqp
doubles); the http CSV codec rounds values to 6 decimals on the wire, so
there the columnar path (which skips the encode/decode) is the
higher-fidelity one.

The host ingest fast path (``ingest_fastpath=True``, default) makes batch
assembly one fleet-wide pass in every mode: ``assemble_windows`` drains
every live env, gathers all drained batches into one set of columns keyed
by (slot, stream) and closes them with a handful of NumPy calls
(``accumulator.FleetAccumulator``) straight into a rotating pool of
preallocated (K, E, S, M) staging buffers (host numpy; donation rules
untouched); the per-window ``"fused"`` mode assembles its one window the
same way. The per-env Accumulators keep their stats there.
``ingest_fastpath=False`` runs one legacy chunk-list + lexsort Accumulator
per env, the reference the fleet pass is bit-identical to — windows,
stats, tie order, drop accounting (tests/test_ingest_fastpath).
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PerceptaPipeline, PipelineConfig
from repro.core.frame import make_raw_window
from repro.runtime import spans
from repro.runtime.accumulator import Accumulator, FleetAccumulator
from repro.runtime.forwarder import ForwarderHub
from repro.runtime.predictor import Predictor
from repro.runtime.prefetch import WindowPrefetcher
from repro.runtime.queues import QueueBroker
from repro.runtime.receivers import Receiver, SimulatedDevice
from repro.runtime.records import RecordBatch
from repro.runtime.translator import Translator

# Manager-loop mode -> device-pipeline mode: the async modes reuse the scan
# engines and differ only in how the Manager overlaps host assembly
_PIPELINE_MODE = {
    "scan_async": "scan",
    "scan_async_sharded": "scan_sharded",
    "scan_fused_decide_async": "scan_fused_decide",
    "scan_fused_decide_async_sharded": "scan_fused_decide_sharded",
}
_FUSED_DECIDE_MODES = ("scan_fused_decide", "scan_fused_decide_sharded",
                       "scan_fused_decide_async",
                       "scan_fused_decide_async_sharded")
_SCAN_MODES = ("scan", "scan_sharded", "scan_async",
               "scan_async_sharded") + _FUSED_DECIDE_MODES
_ASYNC_MODES = ("scan_async", "scan_async_sharded",
                "scan_fused_decide_async", "scan_fused_decide_async_sharded")
# pipeline modes whose dispatch runs under shard_map on the env mesh
_SHARDED_PIPE_MODES = ("scan_sharded", "scan_fused_decide_sharded")


def _window_counts(recs, starts: np.ndarray) -> np.ndarray:
    """Drained records per window of a batch (window starts ``starts``):
    each record counts in the window whose bounds hold its timestamp,
    clipped to the batch, so the counts sum to the drain total."""
    K = len(starts)
    c = np.zeros(K, np.int64)
    scalar_ts = []            # one vectorized pass per drain, not per item
    for r in recs:
        if isinstance(r, RecordBatch):
            j = np.searchsorted(starts, r.timestamps, side="right") - 1
            c += np.bincount(np.clip(j, 0, K - 1), minlength=K)
        else:
            scalar_ts.append(r.timestamp)
    if scalar_ts:
        j = np.searchsorted(starts, np.asarray(scalar_ts), side="right") - 1
        c += np.bincount(np.clip(j, 0, K - 1), minlength=K)
    return c


@dataclass
class SourceSpec:
    source_id: str
    protocol: str                 # mqtt | http | amqp
    device: SimulatedDevice
    unit_scale: float = 1.0


class PerceptaSystem:
    def __init__(self, env_ids: Sequence[str], sources: Sequence[SourceSpec],
                 pipeline_cfg: PipelineConfig, predictor: Predictor,
                 forwarders: Optional[ForwarderHub] = None, db=None,
                 mode: str = "fused", speedup: float = 60.0,
                 t0: float = 0.0, manual_time: bool = False,
                 scan_k=8, ingest: str = "columnar",
                 autotune: Optional[dict] = None,
                 batched_consume: bool = True,
                 contract_check: bool = True,
                 train: Optional[str] = None,
                 train_cfg: Optional[dict] = None,
                 policy=None,
                 env_slots: Optional[int] = None,
                 elastic: bool = False,
                 ingest_fastpath: bool = True):
        # manual_time: the virtual clock only advances when run_windows
        # closes a window — deterministic under arbitrary jit-compile stalls
        # (tests); wall-clock speedup mode is the realistic deployment shape.
        self.manual_time = manual_time
        self._manual_t = t0
        # elastic: the env axis is a padded slot pool; E == env_slots rows,
        # of which only the masked subset is live (module docstring)
        self.elastic = bool(elastic)
        if self.elastic:
            if mode not in _SCAN_MODES:
                raise ValueError(
                    "elastic=True needs a scan engine (the active mask "
                    f"rides the scan dispatch); mode {mode!r} dispatches "
                    "per window")
            slots = int(env_slots) if env_slots is not None \
                else pipeline_cfg.n_envs
            assert len(env_ids) <= slots, (len(env_ids), slots)
            assert pipeline_cfg.n_envs == slots, \
                "elastic: pipeline_cfg.n_envs must equal env_slots " \
                f"({pipeline_cfg.n_envs} != {slots})"
            assert predictor.n_envs == slots, \
                "elastic: build the Predictor at env_slots rows " \
                f"({predictor.n_envs} != {slots})"
            self.env_slots: Optional[int] = slots
            self._slot_env: List[Optional[str]] = \
                list(env_ids) + [None] * (slots - len(env_ids))
            self._free_slots: List[int] = list(range(len(env_ids), slots))
            self._active = np.zeros(slots, bool)
            self._active[:len(env_ids)] = True
            self._prev_ok = np.zeros(slots, bool)
        else:
            assert env_slots is None or env_slots == len(env_ids), \
                "env_slots beyond len(env_ids) requires elastic=True"
            assert pipeline_cfg.n_envs == len(env_ids)
            self.env_slots = None
        self._membership_epoch = 0
        assert pipeline_cfg.n_streams == len(sources)
        self.env_ids = list(env_ids)
        self.sources = list(sources)
        # bake the absolute tick origin in (exact integer seasonal phase
        # under window-relative device timestamps; see core.pipeline)
        pipeline_cfg = dataclasses.replace(
            pipeline_cfg, tick0=int(round(t0 / pipeline_cfg.tick_s)))
        self.cfg = pipeline_cfg
        self.mode = mode
        pipe_mode = _PIPELINE_MODE.get(mode, mode)
        self.fused_decide = mode in _FUSED_DECIDE_MODES
        # policy: a registry name ("linear"|"mlp"|"rglru"|"rwkv6") or
        # runtime.policies.PolicyConfig — rebinds the predictor's model
        # through the certified registry (runtime.policies.build_policy),
        # so the adapter arrives with its PolicyCertificate attached
        if policy is not None:
            predictor.set_model(policy)
        # fused-decide: the decision step is traced into the pipeline scan
        # and the decision state (prev obs/actions, tick, replay ring)
        # becomes part of the device carry — the Predictor hands both over
        # here and only does host bookkeeping (absorb_fused) afterwards
        decide = predictor.make_decide_fn() if self.fused_decide else None
        self._decide = decide
        self._dstate = predictor.decide_state() if self.fused_decide else None
        if self.elastic and self.fused_decide:
            # the elastic mask leaves join the device carry BEFORE the
            # contract check and the pipeline build, so the masked decide
            # path is exactly what gets checked, traced and sharded
            self._dstate = self._dstate._replace(
                active=jnp.asarray(self._active),
                prev_ok=jnp.asarray(self._prev_ok))
        # construction-time invariant gate (ROADMAP item 2): statically
        # check the decision path's jaxpr BEFORE building/compiling the
        # engine, so a cross-env contraction (silent 1-ulp shard
        # divergence), a hidden host callback in the scan body, or a
        # float32 absolute-time cast fails registration with the offending
        # primitive + source line. Env-axis rules bind only under the
        # sharded dispatches (a fused non-sharded build may legally run a
        # non-row-wise model); contract_check=False skips the gate.
        self.contract_check = bool(contract_check)
        if self.contract_check and (self.fused_decide
                                    or pipe_mode in _SHARDED_PIPE_MODES):
            from repro import analysis
            # env rules bind only where the decision math itself runs
            # inside the env-sharded dispatch (fused+sharded); in plain
            # scan_sharded the Predictor consumes on the host, unsharded
            analysis.check_system(
                predictor, decide=decide, dstate=self._dstate,
                sharded=(self.fused_decide
                         and pipe_mode in _SHARDED_PIPE_MODES),
                label=f"PerceptaSystem(mode={mode!r})")
        # fused/sharded modes additionally demand a valid PolicyCertificate
        # for the model itself (repro.analysis.certify): registry policies
        # arrive with one attached (cached — repeated standups skip the
        # trace entirely); an ad-hoc adapter is certified here at the true
        # (E, F, A) shapes, with the env/carry families binding only under
        # the env-sharded dispatch (a fused non-sharded build may legally
        # run a non-row-wise model, e.g. examples/serve_edge.py's LM).
        self.policy_certificate = None
        if self.contract_check and self.fused_decide:
            cert = getattr(predictor.model, "certificate", None)
            if cert is None:
                from repro.analysis import certify
                sharded = pipe_mode in _SHARDED_PIPE_MODES
                cert = certify.certify_policy(
                    predictor.model,
                    ((predictor.n_envs, predictor.n_features,
                      predictor.action_space.n),),
                    name=getattr(predictor.model, "name", None),
                    rules=certify.Rules(env=sharded, collectives=True,
                                        callbacks=True, time=True,
                                        carry=sharded))
                predictor.model.certificate = cert
            self.policy_certificate = cert
        # predictor tick index of this system's window 0: export-time
        # reconstruction maps tick idx -> window (idx - base); ticks issued
        # BEFORE this system keep their host-mirror times
        self._tick_base = int(predictor.stats["ticks"])

        # scan_k="auto": short measured calibration grid over K x mesh split
        self.tuned = None
        mesh = None
        if scan_k == "auto":
            from repro.core.autotune import tune_scan_params
            from repro.distribution import sharding as shard_lib
            kw = dict(autotune or {})
            if pipe_mode not in _SHARDED_PIPE_MODES:
                # mesh splits only apply to the sharded dispatches
                kw.setdefault("device_counts", [1])
            if self.fused_decide:
                # tune the engine that will actually run: the fused scan
                # (pipeline tick + decision step in one dispatch)
                kw.setdefault("decide", decide)
                kw.setdefault("decide_state", self._dstate)
            self.tuned = tune_scan_params(pipeline_cfg, **kw)
            scan_k = self.tuned.scan_k
            if pipe_mode in _SHARDED_PIPE_MODES:
                # honor the measured split even when it is 1 device (the
                # mesh then degenerates to plain scan); leaving mesh=None
                # would silently shard over ALL devices instead
                mesh = shard_lib.env_mesh(
                    pipeline_cfg.n_envs,
                    devices=jax.devices()[:max(1, self.tuned.mesh_devices)])
        self.scan_k = max(1, int(scan_k))
        assert ingest in ("columnar", "records"), ingest
        self.ingest = ingest
        # ingest_fastpath: one fleet-wide assembly pass (bit-identical to
        # the per-env legacy chunk-list + global-lexsort Accumulators,
        # which False keeps alive for before/after benchmarking and parity
        # tests)
        self.ingest_fastpath = bool(ingest_fastpath)
        # (K, E, S, M)-keyed pool of reusable host staging buffers for
        # assemble_windows (see _staging_buffers)
        self._stage_pool: Dict[tuple, dict] = {}
        # scan-mode consume: one Predictor.on_windows dispatch per K-window
        # batch (default); False keeps the per-window on_tick loop — the
        # tested reference path the batched one must match bit for bit
        self.batched_consume = bool(batched_consume)
        # async modes must NOT donate: dispatching with a donated input that
        # is still being computed blocks the dispatch (and the pump thread
        # behind it), serializing the very batches the prefetcher overlaps.
        # Double-buffering two state pytrees is the async design anyway.
        self.pipeline = PerceptaPipeline(
            pipeline_cfg, mode=pipe_mode,
            donate=mode in ("scan", "scan_sharded", "scan_fused_decide",
                            "scan_fused_decide_sharded"),
            mesh=mesh, decide=decide, decide_state=self._dstate,
            elastic=self.elastic)
        self.state = self.pipeline.init_state()
        self._prefetcher: Optional[WindowPrefetcher] = None
        self.predictor = predictor
        # train="online": device-resident retraining interleaved with the
        # fused decide dispatches (runtime.trainer). The trainer needs the
        # decision state in the device carry, so it composes only with the
        # fused-decide modes; train_cfg kwargs pass through to OnlineTrainer
        # (batch_size, train_cfg, seed, checkpoint_dir, checkpoint_every).
        self.trainer = None
        if train is not None:
            if train != "online":
                raise ValueError(f"unknown train mode {train!r} "
                                 "(expected None or 'online')")
            if not self.fused_decide:
                raise ValueError(
                    "train='online' rides the fused decide carry: use a "
                    f"scan_fused_decide* mode, not {mode!r}")
            from repro.runtime.trainer import OnlineTrainer
            kw = dict(train_cfg or {})
            kw.setdefault("contract_check", self.contract_check)
            self.trainer = OnlineTrainer(predictor, **kw)
        self.forwarders = forwarders
        self.db = db
        self.speedup = speedup
        self._wall0 = time.time()
        self._t0 = t0
        self.window_s = pipeline_cfg.n_ticks * pipeline_cfg.tick_s
        self.window_index = 0

        self.broker = QueueBroker()
        self.translators = {
            s.source_id: Translator(s.source_id, s.protocol,
                                    unit_scale=s.unit_scale)
            for s in sources
        }
        self.receivers: List[Receiver] = []
        for s in sources:
            self.receivers.append(
                Receiver(s.source_id, s.protocol, s.device, self.now,
                         speedup=speedup))
        self._stream_names = [s.device.stream for s in sources]
        self.accumulators: Dict[str, Accumulator] = {}
        for env in env_ids:
            self._register_env(env)
        # the fast path closes every env in one pass; the per-env
        # Accumulators then keep only their stats
        self.fleet: Optional[FleetAccumulator] = None
        if self.ingest_fastpath:
            self.fleet = FleetAccumulator(self._stream_names,
                                          pipeline_cfg.max_samples)

    def _register_env(self, env_id: str) -> None:
        """Wire one env into every source Receiver and give it its own
        Accumulator (construction and elastic :meth:`attach_env`)."""
        for r in self.receivers:
            tr = self.translators[r.source_id]

            def on_payload(env_id, payload, _tr=tr):
                rec = _tr.translate(env_id, payload)
                if rec is not None:
                    self.broker.publish(rec)

            def on_batch(env_id, stream, ts, vals, srt=None, _tr=tr):
                batch = _tr.translate_batch(env_id, stream, ts, vals, srt)
                if batch is not None:
                    self.broker.publish(batch)

            if self.ingest == "columnar":
                r.subscribe(env_id, on_batch=on_batch)
            else:
                r.subscribe(env_id, on_payload)
        self.accumulators[env_id] = Accumulator(env_id, self._stream_names,
                                                self.cfg.max_samples)

    def _live_slots(self) -> List[tuple]:
        """``[(slot_row, env_id), ...]`` of the live envs, slot order.

        Non-elastic systems enumerate ``env_ids`` densely; elastic ones
        skip free/inactive slots, so host loops (ingest, close_windows,
        forwarders, DB, stats) never touch a dead row."""
        if not self.elastic:
            return list(enumerate(self.env_ids))
        return [(i, e) for i, e in enumerate(self._slot_env)
                if e is not None and self._active[i]]

    # --- virtual clock -------------------------------------------------------
    def now(self) -> float:
        if self.manual_time:
            return self._manual_t
        return self._t0 + (time.time() - self._wall0) * self.speedup

    def window_bounds(self, index: Optional[int] = None):
        idx = self.window_index if index is None else index
        start = self._t0 + idx * self.window_s
        return start, start + self.window_s

    # --- threaded operation ---------------------------------------------------
    def start(self):
        for r in self.receivers:
            r.start()

    def stop(self):
        for r in self.receivers:
            r.stop()
        if self._prefetcher is not None:
            self._prefetcher.stop()
        if self.trainer is not None:
            self.trainer.close()

    # --- synchronous operation (benchmarks / tests) ---------------------------
    def pump_receivers(self):
        for r in self.receivers:
            r.poll_once()

    def run_window(self) -> dict:
        """Process one closed window across all environments."""
        t_start, t_end = self.window_bounds()
        E = self.cfg.n_envs
        (values, ts, valid), counts = self._assemble([(t_start, t_end)])

        t_proc0 = time.time()
        raw = make_raw_window(values[0], ts[0], valid[0])
        # window-relative time: timestamps were rebased to this window's
        # start, so the device sees window_start = 0 (float32-exact on any
        # horizon); absolute time stays host-side (t_end below)
        self.state, feats, frame = self.pipeline.run_tick(
            self.state, raw, jnp.zeros((E,), jnp.float32))
        actions, rewards, per_term = self.predictor.on_tick(
            feats.features, t_end, raw=feats.raw)
        latency = time.time() - t_proc0

        if self.forwarders is not None:
            for i, env in enumerate(self.env_ids):
                self.forwarders.dispatch(env, t_end, actions[i])
        if self.db is not None:
            obs = np.asarray(feats.features)
            ver = int(self.predictor.policy_version)
            for i, env in enumerate(self.env_ids):
                self.db.append(env, t_end, obs[i], actions[i],
                               float(rewards[i]),
                               extra={"policy_version": ver})

        self.window_index += 1
        return {
            "window": self.window_index - 1,
            "records": counts[0],
            "latency_s": latency,
            "mean_reward": float(np.mean(rewards)),
            "observed_frac": float(np.asarray(frame.observed).mean()),
            "filled_frac": float(np.asarray(frame.filled).mean()),
            "anomalous": int(np.asarray(frame.anomalous).sum()),
        }

    # --- scan-fused operation --------------------------------------------------
    # Staging buffers alive at once in the deepest pipeline (async modes):
    # when the Manager takes batch j from the depth-1 ready buffer, the
    # pump stages j+1 and starts assembling j+2 while batch j-1 — consumed
    # only after that take — may still be executing: four batches.
    # ``jnp.asarray`` may alias a host buffer (and a host-to-device copy
    # reads it asynchronously), so a buffer is only reused once its batch
    # is provably consumed — with depth 4 the epoch reusing buffer b%4
    # starts only after batch b-4's results were consumed.
    _STAGE_DEPTH = 4

    def _staging_buffers(self, K: int, E: int):
        """Rotating preallocated (K, E, S, M) staging triple, zeroed.

        One allocation per (shape, rotation slot) for the lifetime of the
        system: steady-state assembly reuses the arrays (a memset instead
        of three fresh allocations per batch). Cleared on :meth:`resize`
        (the env width changes)."""
        S, M = self.cfg.n_streams, self.cfg.max_samples
        pool = self._stage_pool.setdefault((K, E, S, M),
                                           {"bufs": [], "next": 0})
        i = pool["next"]
        pool["next"] = (i + 1) % self._STAGE_DEPTH
        if i >= len(pool["bufs"]):
            shape = (K, E, S, M)
            pool["bufs"].append((np.zeros(shape, np.float32),
                                 np.zeros(shape, np.float32),
                                 np.zeros(shape, bool)))
        else:
            for a in pool["bufs"][i]:
                a.fill(0)
        return pool["bufs"][i]

    def _assemble_env(self, slot: int, env: str, bounds, starts,
                      values, ts, valid, tally: list = None) -> np.ndarray:
        """Drain, count, ingest and close ONE env into its staging rows
        (``ingest_fastpath=False``: the per-env legacy reference).

        Given a ``tally`` (a traced batch), the steps are clocked: drain,
        count + ingest and close seconds add to ``tally[0:3]``, the longest
        queue wait is kept in ``tally[3]``."""
        timed = tally is not None
        q = self.broker.queue_for(env)
        t0 = time.perf_counter() if timed else 0.0
        recs = q.drain()
        t1 = time.perf_counter() if timed else 0.0
        c = _window_counts(recs, starts)
        acc = self.accumulators[env]
        acc.ingest(recs)
        t2 = time.perf_counter() if timed else 0.0
        acc.close_windows(bounds, rebase=True,
                          out=(values[:, slot], ts[:, slot], valid[:, slot]))
        if timed:
            t3 = time.perf_counter()
            tally[0] += t1 - t0
            tally[1] += t2 - t1
            tally[2] += t3 - t2
            if q.drained_since is not None:
                tally[3] = max(tally[3], t1 - q.drained_since)
        return c

    def _assemble_fleet(self, live, bounds, starts, out,
                        tally: list = None) -> np.ndarray:
        """Drain every live env, then gather and close the whole fleet in
        one pass (``FleetAccumulator``). ``tally`` as in
        :meth:`_assemble_env`."""
        timed = tally is not None
        t0 = time.perf_counter() if timed else 0.0
        drained = []
        for slot, env in live:
            q = self.broker.queue_for(env)
            items = q.drain()
            if items:
                drained.append((slot, items))
            if timed and q.drained_since is not None:
                tally[3] = max(tally[3],
                               time.perf_counter() - q.drained_since)
        t1 = time.perf_counter() if timed else 0.0
        stats = {slot: self.accumulators[env].stats for slot, env in live}
        counts = self.fleet.ingest(drained, starts, stats)
        t2 = time.perf_counter() if timed else 0.0
        self.fleet.close_windows(bounds, out, stats)
        if timed:
            t3 = time.perf_counter()
            tally[0] += t1 - t0
            tally[1] += t2 - t1
            tally[2] += t3 - t2
        return counts

    def assemble_windows(self, bounds) -> tuple:
        """Drain queues once and stack K closed windows per env — one pass
        straight into preallocated (K, E, S, M) staging buffers.

        Returns ``(RawWindow with leading K axis, per_window_counts)`` where
        the counts attribute each drained record to the window whose bounds
        contain its timestamp (clipped to the batch, so the counts sum to
        the drain total — mirroring fused mode's per-window ingest numbers
        for consumers like dead-source detection). Per-env isolation is
        structural: each record is keyed by its env's slot from queue to
        staging row ``slot``; no record ever lands in another env's row.
        Inactive/free slots keep their all-invalid zero rows: on device
        their state updates are natural no-ops and outputs are masked.

        The fast path (``ingest_fastpath=True``) drains every live env and
        then gathers and closes the whole fleet with one
        ``FleetAccumulator`` pass; ``ingest_fastpath=False`` runs the
        per-env legacy Accumulators, the bit-identity reference.
        """
        (values, ts, valid), counts = self._assemble(bounds)
        return make_raw_window(values, ts, valid), counts

    def _assemble(self, bounds) -> tuple:
        """:meth:`assemble_windows` on the host: the staging triple and the
        per-window counts."""
        with spans.span("percepta.assemble") as sp:
            K = len(bounds)
            starts = np.asarray([b[0] for b in bounds], np.float64)
            live = self._live_slots()
            values, ts, valid = self._staging_buffers(K, self.cfg.n_envs)
            # the steps are clocked only while a profiler records
            tally = [0.0, 0.0, 0.0, 0.0] if spans.tracing() else None
            if self.fleet is not None:
                counts_arr = self._assemble_fleet(
                    live, bounds, starts, (values, ts, valid), tally)
            else:
                counts_arr = np.zeros(K, np.int64)
                for i, env in live:
                    counts_arr += self._assemble_env(
                        i, env, bounds, starts, values, ts, valid, tally)
            counts = [int(c) for c in counts_arr]
            if tally is not None:
                drain_ms, ingest_ms, close_ms, wait_ms = (1e3 * t
                                                          for t in tally)
                meta = {}
                if self.fleet is not None:
                    meta = {"sort_fallbacks": self.fleet.sort_fallback,
                            "carried": self.fleet.pending()}
                sp.set_metadata(
                    records=sum(counts), envs=len(live), drain_ms=drain_ms,
                    ingest_ms=ingest_ms, close_ms=close_ms,
                    queue_wait_ms=wait_ms,
                    staged_bytes=values.nbytes + ts.nbytes + valid.nbytes,
                    **meta)
            return (values, ts, valid), counts

    def run_windows_scan(self, k: int) -> List[dict]:
        """Process the next ``k`` windows with ONE device dispatch."""
        with spans.span("percepta.batch", k=k, window=self.window_index):
            bounds = [self.window_bounds(self.window_index + j)
                      for j in range(k)]
            raw, counts = self.assemble_windows(bounds)
            if self.fused_decide:
                outs, t_dispatch, ver = self._dispatch_decide(raw, k)
                return self._consume_decide(bounds, counts, outs,
                                            t_dispatch, ver)
            feats, frames, t_dispatch = self._dispatch_scan(raw, k)
            return self._consume_scan(bounds, counts, feats, frames,
                                      t_dispatch)

    @spans.spanned("percepta.dispatch")
    def _dispatch_scan(self, raw, k: int):
        """Launch ONE ``run_many`` over a staged K-window batch (no block:
        JAX async dispatch returns futures; consumption blocks)."""
        t_dispatch = time.time()
        # window-relative time: each window's samples were rebased to its
        # own start by close_windows, so every scan step sees start = 0
        starts = jnp.zeros((k, self.cfg.n_envs), jnp.float32)
        self.state, feats, frames = self.pipeline.run_many(
            self.state, raw, starts,
            active=jnp.asarray(self._active) if self.elastic else None)
        return feats, frames, t_dispatch

    @spans.spanned("percepta.consume")
    def _consume_scan(self, bounds, counts, feats, frames,
                      t_dispatch) -> List[dict]:
        """Block on a dispatched batch and run the batch host side
        (Predictor, Forwarders, DB) in window order.

        The Predictor consumes the whole K-window stack in ONE jitted
        dispatch (``on_windows`` over the stacked device features — the
        same fusion ``run_many`` applies to the pipeline, applied to the
        decision path), then the per-window loop only slices numpy for
        Forwarders/DB/result rows. ``batched_consume=False`` keeps the
        per-window ``on_tick`` loop as the tested reference; both paths
        are bit-identical (asserted in tests/test_predictor_batch.py).
        """
        k = len(bounds)
        # elastic: host sinks and stats see only the live rows (compacted,
        # slot order == attach order of the current membership); the
        # predictor gets the dense masked stack plus the mask itself
        if self.elastic:
            live = self._live_slots()
            rows: Optional[np.ndarray] = np.asarray([i for i, _ in live],
                                                    np.int64)
            ids = [e for _, e in live]
        else:
            rows, ids = None, self.env_ids
        if self.batched_consume:
            # feed the stacked DEVICE features straight into the predictor
            # scan — one dispatch, one host transfer per output leaf
            actions_b, rewards_b, _ = self.predictor.on_windows(
                feats.features, [b[1] for b in bounds], raw=feats.raw,
                active=self._active if self.elastic else None,
                prev_ok=self._prev_ok if self.elastic else None)
            if self.elastic:
                # host mirror of the device-side first-window chain rule
                self._prev_ok = self._prev_ok | self._active
            batch_latency = time.time() - t_dispatch
        else:
            jax.block_until_ready(feats.features)
            batch_latency = time.time() - t_dispatch
            raw_np = np.asarray(feats.raw)

        out = []
        # one batch-wide host transfer per leaf; the per-window loop then
        # slices numpy — per-window DEVICE slicing (feats.features[j]) costs
        # two extra device dispatches per window and, in async mode, queues
        # them behind the next batch's scan
        feat_np = np.asarray(feats.features)
        obs_np = np.asarray(frames.observed)
        fill_np = np.asarray(frames.filled)
        anom_np = np.asarray(frames.anomalous)
        for j, (t_start, t_end) in enumerate(bounds):
            t_host0 = time.time()
            if self.batched_consume:
                actions, rewards = actions_b[j], rewards_b[j]
            else:
                # reference path: the per-window dispatch stays inside the
                # timed region so latency_s keeps counting Predictor time
                actions, rewards, _ = self.predictor.on_tick(
                    feat_np[j], t_end, raw=raw_np[j],
                    active=self._active if self.elastic else None,
                    prev_ok=self._prev_ok if self.elastic else None)
                if self.elastic:
                    self._prev_ok = self._prev_ok | self._active
            if rows is not None:
                # compact to the live rows: Forwarders/DB/stats must never
                # see (or average over) a dead slot's masked zeros
                actions, rewards = actions[rows], rewards[rows]
                feat_j = feat_np[j][rows]
                obs_j, fill_j, anom_j = (obs_np[j][rows], fill_np[j][rows],
                                         anom_np[j][rows])
            else:
                feat_j = feat_np[j]
                obs_j, fill_j, anom_j = obs_np[j], fill_np[j], anom_np[j]
            if self.forwarders is not None:
                self.forwarders.dispatch_window(t_end, actions)
            if self.db is not None:
                self.db.append_many(ids, t_end, feat_j, actions,
                                    rewards,
                                    extra={"policy_version":
                                           int(self.predictor.policy_version)})
            self.window_index += 1
            # comparable to run_window's latency_s: amortized device +
            # predictor share of the batch plus this window's host work
            latency = batch_latency / k + (time.time() - t_host0)
            out.append({
                "window": self.window_index - 1,
                "records": counts[j],
                "latency_s": latency,
                "mean_reward": float(np.mean(rewards)) if rewards.size
                               else 0.0,
                "observed_frac": float(obs_j.mean()) if obs_j.size else 0.0,
                "filled_frac": float(fill_j.mean()) if fill_j.size else 0.0,
                "anomalous": int(anom_j.sum()),
            })
        return out

    # --- fused-decide operation ------------------------------------------------
    def _dispatch_decide(self, raw, k: int):
        """Launch ONE fused pipeline+decision dispatch over a staged
        K-window batch: features flow straight into the policy/validate/
        reward/replay step inside the scan, and BOTH carries (pipeline
        state + decide state) stay device-resident (donated in the sync
        modes). No block — consumption blocks.

        With an attached trainer this is the batch boundary: the previous
        train step's result hot-swaps the carry's policy/version leaves
        BEFORE the dispatch (so the whole batch runs one policy), and a
        new train step enqueues right AFTER it (so it fills the dispatch
        bubble instead of delaying serving — the PR 3 priority-inversion
        lesson). Returns ``(outs, t_dispatch, policy_version)`` with the
        version that produced this batch's actions."""
        with spans.span("percepta.dispatch"):
            if self.trainer is not None:
                with spans.span("percepta.train.apply"):
                    self._dstate = self.trainer.apply_pending(self._dstate)
            ver = int(self.predictor.policy_version)
            t_dispatch = time.time()
            starts = jnp.zeros((k, self.cfg.n_envs), jnp.float32)
            with spans.span("percepta.fused_step"):
                self.state, self._dstate, outs = \
                    self.pipeline.run_many_decide(self.state, self._dstate,
                                                  raw, starts)
            if self.elastic:
                # host mirror of the device-side post-scan update
                # (prev_ok = prev_ok | active, see run_many_decide)
                self._prev_ok = self._prev_ok | self._active
            if self.trainer is not None:
                with spans.span("percepta.train.dispatch"):
                    self.trainer.dispatch(self._dstate)
        return outs, t_dispatch, ver

    @spans.spanned("percepta.consume")
    def _consume_decide(self, bounds, counts, outs, t_dispatch,
                        version: int = 0) -> List[dict]:
        """Drain host sinks from the SMALL fused outputs.

        The host fetches only actions (K, E, A), rewards (K, E), violation
        flags and the per-env int32 observed/filled/anomalous counts — the
        (K, E, F) feature stack is fetched ONLY when a LogDB needs obs
        rows, and the (K, E, S, T) frames never leave the device (the
        fractions divide the exact counts, bit-identical to ``np.mean``
        over the full frame). Every window is forwarded, then every window
        logged: each sink sees its windows in order."""
        k = len(bounds)
        with spans.span("percepta.result_wait"):
            actions_b = np.asarray(outs.actions)   # blocks on the batch
        batch_latency = time.time() - t_dispatch
        rewards_b = np.asarray(outs.rewards)
        obs_c = np.asarray(outs.observed)
        fill_c = np.asarray(outs.filled)
        anom_c = np.asarray(outs.anomalous)
        feat_np = np.asarray(outs.features) if self.db is not None else None
        self.predictor.absorb_fused([b[1] for b in bounds],
                                    np.asarray(outs.violated))
        # elastic: stats normalize by the LIVE row count (frame counts from
        # inactive rows are masked zeros on device, so whole-array sums are
        # already live-only); sinks get the compacted live rows
        if self.elastic:
            live = self._live_slots()
            rows: Optional[np.ndarray] = np.asarray([i for i, _ in live],
                                                    np.int64)
            ids = [e for _, e in live]
            n_rows = max(len(live), 1)
        else:
            rows, ids, n_rows = None, self.env_ids, self.cfg.n_envs
        if rows is not None:
            actions_b, rewards_b = actions_b[:, rows], rewards_b[:, rows]
            if feat_np is not None:
                feat_np = feat_np[:, rows]
        # each window's own host seconds in the sinks, for its latency_s
        host_s = [0.0] * k
        if self.forwarders is not None:
            with spans.span("percepta.forward"):
                for j, (_, t_end) in enumerate(bounds):
                    t0 = time.time()
                    self.forwarders.dispatch_window(t_end, actions_b[j])
                    host_s[j] += time.time() - t0
        if self.db is not None:
            with spans.span("percepta.log"):
                for j, (_, t_end) in enumerate(bounds):
                    t0 = time.time()
                    self.db.append_many(ids, t_end, feat_np[j], actions_b[j],
                                        rewards_b[j],
                                        extra={"policy_version": version})
                    host_s[j] += time.time() - t0
        denom = float(n_rows * self.cfg.n_streams * self.cfg.n_ticks)
        out = []
        for j in range(k):
            rewards = rewards_b[j]
            self.window_index += 1
            out.append({
                "window": self.window_index - 1,
                "records": counts[j],
                "latency_s": batch_latency / k + host_s[j],
                "mean_reward": float(np.mean(rewards)) if rewards.size
                               else 0.0,
                # exact integer counts / float64 size == np.mean over the
                # live rows of the (E, S, T) bool frame, bit for bit
                "observed_frac": float(int(obs_c[j].sum()) / denom),
                "filled_frac": float(int(fill_c[j].sum()) / denom),
                "anomalous": int(anom_c[j].sum()),
            })
        return out

    def _dispatch_batch(self, batch):
        """Mode-dispatching async helper: launch one assembled batch and
        return the pending tuple ``_consume_batch`` expects."""
        k = len(batch.bounds)
        if self.fused_decide:
            outs, td, ver = self._dispatch_decide(batch.raw, k)
            return (batch.bounds, batch.counts, outs, td, ver)
        feats, frames, td = self._dispatch_scan(batch.raw, k)
        return (batch.bounds, batch.counts, feats, frames, td)

    def _consume_batch(self, pending) -> List[dict]:
        if self.fused_decide:
            return self._consume_decide(*pending)
        return self._consume_scan(*pending)

    def _advance_clock(self, t_end: float):
        if self.manual_time:
            self._manual_t = t_end + 1e-3
        else:
            while self.now() < t_end:
                time.sleep(0.001)

    # --- elastic membership (attach / detach / regrow) -------------------------
    def _assert_membership_boundary(self):
        assert self.elastic, "attach/detach/resize require elastic=True"
        if self._prefetcher is not None:
            assert self._prefetcher.in_flight() == 0, \
                "membership changes only at batch boundaries: a window " \
                "batch plan is still in flight (finish run_windows first)"

    def _refresh_env_ids(self):
        self.env_ids = [e for _, e in self._live_slots()]

    def _export_env_ids(self) -> List[str]:
        """Slot-table env ids at the FULL pool width (replay export keys
        rows by slot; free slots get a placeholder that never matches a
        valid row)."""
        if not self.elastic:
            return self.env_ids
        return [e if e is not None else f"__slot{i}__"
                for i, e in enumerate(self._slot_env)]

    def attach_env(self, env_id: str) -> int:
        """Join a new env into a free slot between window batches.

        No retrace: only the ``active`` mask value changes. The slot's
        pipeline-state rows are reset from a fresh init template (the init
        sentinels — ``prev_ts``, norm min/max — are NOT zeros), its decide
        rows are scrubbed, and its receiver subscriptions start a fresh
        poll horizon at attach time. Grows the pool first when it is full.
        Returns the slot row."""
        self._assert_membership_boundary()
        assert env_id not in self.accumulators, \
            f"env {env_id!r} is already attached"
        if not self._free_slots:
            self.resize()
        slot = self._free_slots.pop(0)
        self._slot_env[slot] = env_id
        self._active[slot] = True
        self._prev_ok[slot] = False
        self._register_env(env_id)
        from repro.distribution import elastic as elastic_lib
        self.state = elastic_lib.reset_env_rows(
            self.state, self.pipeline.init_state(), [slot])
        if self.fused_decide:
            self._dstate = self._reset_dstate_rows(self._dstate, slot)
        else:
            self.predictor.clear_env_rows([slot])
        self._refresh_env_ids()
        self._membership_epoch += 1
        return slot

    def detach_env(self, env_id: str) -> int:
        """Remove a live env, freeing its slot for reuse.

        Host plumbing is torn down (receiver subscriptions, queue,
        accumulator — pending records are discarded) and the slot's decide
        rows / replay validity are scrubbed so a later tenant never
        observes the departed env's data. Returns the freed slot row."""
        self._assert_membership_boundary()
        assert env_id in self.accumulators, f"env {env_id!r} is not attached"
        slot = self._slot_env.index(env_id)
        for r in self.receivers:
            r.unsubscribe(env_id)
        self.broker.remove(env_id)
        self.accumulators.pop(env_id).reset()
        if self.fleet is not None:
            self.fleet.discard(slot)
        self._slot_env[slot] = None
        self._active[slot] = False
        self._prev_ok[slot] = False
        bisect.insort(self._free_slots, slot)
        if self.fused_decide:
            self._dstate = self._reset_dstate_rows(self._dstate, slot)
        else:
            self.predictor.clear_env_rows([slot])
        self._refresh_env_ids()
        self._membership_epoch += 1
        return slot

    def _reset_dstate_rows(self, d, slot: int):
        """Scrub one slot's rows of the fused decide carry and refresh the
        mask leaves from the host mirrors (out-of-place ``.at`` updates
        between dispatches — donation aliasing is never violated)."""
        d = d._replace(
            prev_obs=d.prev_obs.at[slot].set(0.0),
            prev_actions=d.prev_actions.at[slot].set(0.0),
            replay=d.replay._replace(
                valid=d.replay.valid.at[slot].set(False)),
            active=jnp.asarray(self._active),
            prev_ok=jnp.asarray(self._prev_ok))
        model = self.predictor.model
        if d.carry is not None and model.init_carry is not None:
            tmpl = model.init_carry(self.cfg.n_envs)
            d = d._replace(carry=jax.tree.map(
                lambda x, t: x.at[slot].set(jnp.asarray(t)[slot]),
                d.carry, tmpl))
        return d

    def resize(self, new_slots: Optional[int] = None) -> int:
        """Grow the slot pool (the ONE allowed retrace point).

        Protocol (module docstring / distribution.elastic): flush any
        pending train step into the carry, pad every env-leading pytree
        against a fresh init template at the new width, rebuild the engine
        at the new shapes, and re-place state + decide carry on the
        re-chosen env mesh in the sharded modes. Surviving rows are
        byte-for-byte preserved, so live envs resume bit-exactly."""
        self._assert_membership_boundary()
        from repro.distribution import elastic as elastic_lib
        from repro.distribution import sharding as shard_lib
        old = self.env_slots
        pipe_mode = _PIPELINE_MODE.get(self.mode, self.mode)
        sharded = pipe_mode in _SHARDED_PIPE_MODES
        ndev = len(jax.devices()) if sharded else 1
        if new_slots is None:
            new_slots = elastic_lib.next_pool_size(old + 1, old, ndev)
        assert new_slots > old, (new_slots, old)
        if self.trainer is not None:
            # a train step dispatched against the old-width carry must land
            # before the carry is grown under it
            self._dstate = self.trainer.flush_pending(self._dstate)
        pad = new_slots - old
        self._active = np.concatenate([self._active, np.zeros(pad, bool)])
        self._prev_ok = np.concatenate([self._prev_ok, np.zeros(pad, bool)])
        self._slot_env.extend([None] * pad)
        self._free_slots.extend(range(old, new_slots))
        if self.fused_decide:
            # the predictor's replay/model-carry mirrors are stale donated
            # snapshots in fused modes (module docstring): refresh them from
            # the live carry so grow_envs concatenates real buffers and the
            # decide_state() template below is materialized at new width
            self.predictor.replay = self._dstate.replay
            self.predictor._prev["obs"] = np.asarray(self._dstate.prev_obs)
            self.predictor._prev["actions"] = \
                np.asarray(self._dstate.prev_actions)
            if self._dstate.carry is not None:
                self.predictor._model_carry = self._dstate.carry
        self.predictor.grow_envs(new_slots)
        new_cfg = dataclasses.replace(self.cfg, n_envs=new_slots)
        mesh = shard_lib.env_mesh(new_slots) if sharded else None
        if self.fused_decide:
            # grow against the predictor's fresh-template carry: the mask
            # leaves are None there, so strip ours first (same pytree
            # structure), then re-set them at the new width
            d = self._dstate._replace(active=None, prev_ok=None)
            d = elastic_lib.grow_env_tree(d, self.predictor.decide_state(),
                                          old)
            self._dstate = d._replace(active=jnp.asarray(self._active),
                                      prev_ok=jnp.asarray(self._prev_ok))
        self.cfg = new_cfg
        self.pipeline = PerceptaPipeline(
            new_cfg, mode=pipe_mode,
            donate=self.mode in ("scan", "scan_sharded", "scan_fused_decide",
                                 "scan_fused_decide_sharded"),
            mesh=mesh, decide=self._decide,
            decide_state=self._dstate if self.fused_decide else None,
            elastic=True)
        self.state = elastic_lib.grow_env_tree(
            self.state, self.pipeline.init_state(), old)
        self.env_slots = new_slots
        # staging buffers are keyed by (K, E, S, M); the env width just
        # changed, so drop the old-width pool (rebuilt lazily)
        self._stage_pool.clear()
        if mesh is not None:
            self.state = shard_lib.place_env_tree(self.state, 0, mesh)
            if self.fused_decide:
                # decide_specs, not the rank rule: policy weights must stay
                # replicated even when their leading dim divides the pool
                specs = shard_lib.decide_specs(self._dstate, 0,
                                               mesh.axis_names[0])
                self._dstate = shard_lib.place_env_tree(
                    self._dstate, 0, mesh, specs=specs)
        self._membership_epoch += 1
        return new_slots

    # --- donation-safe state access -------------------------------------------
    def snapshot_state(self):
        """Deep copy of the pipeline state pytree, safe to hold across windows.

        ``scan``/``scan_sharded`` donate the state buffers into every
        ``run_many`` dispatch, so a bare ``system.state.<leaf>`` reference
        becomes invalid after the next window batch; this accessor hands out
        copies so callers never have to reason about donation.
        """
        return jax.tree.map(lambda x: jnp.array(x, copy=True), self.state)

    def snapshot_norm(self):
        """Donation-safe copy of just the normalizer stats (NormState)."""
        return jax.tree.map(lambda x: jnp.array(x, copy=True),
                            self.state.norm)

    def snapshot_decide(self):
        """Deep copy of the fused decision carry (``DecideState``), safe to
        hold across window batches. Fused-decide modes donate the carry —
        including the replay ring — into every dispatch, so bare
        ``system._dstate`` leaf references become invalid after the next
        batch; this is the replay-path twin of :meth:`snapshot_state`."""
        assert self.fused_decide, "snapshot_decide: not a fused-decide mode"
        return jax.tree.map(lambda x: jnp.array(x, copy=True), self._dstate)

    def replay_size(self) -> int:
        """Live transition count of the replay ring, any mode."""
        buf = (self._dstate.replay if self.fused_decide
               else self.predictor.replay)
        return min(int(buf.cursor), buf.capacity)

    def policy_version(self) -> int:
        """Current monotone policy version (0 until a train step applies).

        Every replay row and LogDB row carries the version that produced
        its action, so exports are attributable per row; swaps land only
        at batch boundaries, so all K windows of a batch share one
        version."""
        return int(self.predictor.policy_version)

    def snapshot_policy(self):
        """Donation-safe copy of the LIVE policy params (the device carry's
        ``policy`` leaves in fused-decide modes, the predictor's host
        mirror otherwise)."""
        src = (self._dstate.policy if self.fused_decide
               else self.predictor.policy_params)
        return jax.tree.map(lambda x: jnp.array(x, copy=True), src)

    def train_stats(self) -> Optional[dict]:
        """Trainer counters (dispatched/applied/skipped_empty, last loss
        and grad norm, current version); None when training is off."""
        return None if self.trainer is None else self.trainer.train_stats()

    def restore_training(self):
        """Crash recovery: restore the newest trainer checkpoint into the
        LIVE serving path — trainer state, predictor host mirror, AND the
        device carry's policy/version leaves (``trainer.restore_latest``
        alone only covers the host side; the carry would keep serving the
        construction-time weights). Returns ``(step, params, extra)`` or
        ``None`` when no checkpoint exists."""
        if self.trainer is None:
            raise ValueError("restore_training: system built without "
                             "train='online'")
        out = self.trainer.restore_latest()
        if out is None:
            return None
        _, params, _ = out
        self._dstate = self._dstate._replace(
            policy=jax.tree.map(jnp.asarray, params),
            version=jnp.asarray(self.trainer.version, jnp.int32))
        return out

    def export_replay(self, salt: str) -> dict:
        """Anonymized chronological replay export, any mode.

        Non-fused modes delegate to ``Predictor.export_replay`` (host
        float64 mirror re-attached). Fused-decide modes snapshot the
        device carry WITHOUT donating it and reconstruct the exact float64
        absolute time of every system-era transition from its stored int32
        tick index: tick ``idx`` is this system's window ``idx - base``
        (``base`` = the predictor's tick count at construction), and
        windows are consecutive by construction, so it ended at
        ``(t0 + (idx - base) * window_s) + window_s`` — evaluated in
        float64 with exactly :meth:`window_bounds`' operation order, which
        makes the reconstruction bit-identical to the mirror the per-step
        paths maintain. Slots written BEFORE this system existed (a
        Predictor with prior ``on_tick``/``on_windows`` history) keep
        their host-mirror times — their windows were not this system's."""
        if not self.fused_decide:
            return self.predictor.export_replay(self._export_env_ids(), salt)
        from repro.core import replay as rp
        buf = self.snapshot_decide().replay
        # every env row shares the batch-wide tick index, so row 0 carries
        # the slot-aligned index ring; dead slots are never selected by the
        # export's chronological order
        idx_i = np.asarray(buf.tick_idx[0])
        idx = (idx_i - self._tick_base).astype(np.float64)
        recon = (self._t0 + idx * self.window_s) + self.window_s
        slot_times = np.where(idx_i >= self._tick_base, recon,
                              self.predictor._replay_times)
        return rp.export_for_training(buf, self._export_env_ids(), salt,
                                      slot_times=slot_times)

    def run_windows(self, n: int, pump: bool = True) -> List[dict]:
        with spans.span("percepta.run_windows", n=n):
            return self._run_windows(n, pump)

    def _run_windows(self, n: int, pump: bool) -> List[dict]:
        if self.mode in _ASYNC_MODES:
            return self._run_windows_async(n, pump)
        if self.mode in _SCAN_MODES:
            out: List[dict] = []
            while len(out) < n:
                k = min(self.scan_k, n - len(out))
                if pump:
                    # advance past the LAST window of the batch so every
                    # window's samples exist before the single drain
                    t_end = self.window_bounds(self.window_index + k - 1)[1]
                    self._advance_clock(t_end)
                    self.pump_receivers()
                out.extend(self.run_windows_scan(k))
            return out
        out = []
        for _ in range(n):
            if pump:
                # synchronous mode: advance the virtual clock past the window
                # end, then poll every receiver once
                self._advance_clock(self.window_bounds()[1])
                self.pump_receivers()
            out.append(self.run_window())
        return out

    # --- pipelined (async) operation ------------------------------------------
    def _assemble_for_prefetch(self, bounds, pump: bool):
        """Pump-thread body: exactly the synchronous per-batch sequence
        (clock advance -> receiver poll -> drain/close) at the same window
        boundaries — the deterministic handoff that makes ``scan_async``
        bit-identical to ``scan``."""
        if pump:
            self._advance_clock(bounds[-1][1])
            self.pump_receivers()
        return self.assemble_windows(bounds)

    def _run_windows_async(self, n: int, pump: bool = True) -> List[dict]:
        """Double-buffered Manager loop: while batch j runs on device, the
        pump thread assembles batch j+1 and the host consumes batch j-1.

        Batch boundaries (``min(scan_k, remaining)``) match the synchronous
        scan loop exactly, so the drain epochs — and therefore the outputs —
        are identical."""
        if self._prefetcher is None:
            self._prefetcher = WindowPrefetcher(self._assemble_for_prefetch)
        plans, idx, left = [], self.window_index, n
        while left > 0:
            k = min(self.scan_k, left)
            plans.append([self.window_bounds(idx + j) for j in range(k)])
            idx, left = idx + k, left - k
        for bounds in plans:
            self._prefetcher.submit(bounds, pump=pump,
                                    membership=self._membership_epoch)

        out: List[dict] = []
        pending = None
        for _ in plans:
            batch = self._prefetcher.next_batch()
            assert batch.membership == self._membership_epoch, \
                "membership changed while a batch plan was in flight " \
                f"(plan built under epoch {batch.membership}, now " \
                f"{self._membership_epoch}); attach/detach/resize only " \
                "between run_windows calls"
            # consume j-1 BEFORE dispatching j: the Predictor's per-window
            # steps are device computations too, and the single device
            # executes its queue in order — dispatching batch j first would
            # make window j-1's small steps wait behind batch j's big scan
            # (a priority inversion that serializes the whole loop). In the
            # fused-decide composition consume is pure host-sink draining,
            # so the order only matters for result sequencing there.
            if pending is not None:
                out.extend(self._consume_batch(pending))
            pending = self._dispatch_batch(batch)
        out.extend(self._consume_batch(pending))
        return out

    def stats(self) -> dict:
        return {
            "queues": self.broker.stats(),
            "receivers": {r.source_id: r.stats for r in self.receivers},
            "translators": {t.source_id: t.stats
                            for t in self.translators.values()},
            "predictor": self.predictor.stats,
        }
