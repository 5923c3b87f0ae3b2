"""END-TO-END DRIVER (the paper's kind: real-time inference support).

Percepta at the edge with a CERTIFIED registry policy driving decisions
and a real transformer serving ad-hoc requests: simulated MQTT/HTTP/AMQP
devices -> Receivers -> Translators -> env queues -> Accumulator -> fused
device tick (harmonize/gap-fill/de-spike/normalize) -> rg-LRU recurrent
policy -> decisions -> reward -> replay + LogDB -> Forwarders, while a
qwen3-family LM (reduced config) answers batched text requests through
the continuous-batching engine between ticks.

The decision model comes from the policy registry
(``repro.runtime.policies``): ``PerceptaSystem(..., policy="rglru")``
resolves the name to a builder at the system's env/feature/action shapes
and statically CERTIFIES it at registration (``repro.analysis.certify``)
— row-wise env math, recurrent-carry row stability across the decide-step
fixed point, pallas BlockSpec env routing, param replication — before the
fused/sharded engines will accept it. The rg-LRU's recurrent state rides
the donated device carry (``DecideState.carry``) through the fused scan,
env-sharded on the mesh in the ``_sharded`` compositions. Pass a
``PolicyConfig`` to override builder kwargs, e.g.
``PolicyConfig("rglru", {"hidden": 32, "use_pallas": True})`` to run the
hidden-state update through the pallas kernel (``kernels/rglru_scan``) —
bit-identical to the ``lax.scan`` reference, and certifiable because the
checker recurses into ``pallas_call``.

The Percepta tick runs in ``scan`` mode by default: the Manager batches
``SCAN_K`` windows per device dispatch (``PerceptaPipeline.run_many`` —
one ``lax.scan`` with the state carried on device). ``--mode fused``
dispatches one jitted tick per window; ``--mode scan_sharded`` runs the
same scan under ``shard_map`` with envs sharded over the local device
mesh (on one CPU device it degenerates to ``scan``; force a multi-device
CPU mesh with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
before launch); ``--mode scan_async`` overlaps host ingest with device
compute. ``--mode scan_fused_decide`` (and its ``_sharded`` / ``_async``
/ ``_async_sharded`` compositions) fuses the DECISION path into the same
dispatch: policy, action validation, rewards and the replay-ring write
execute inside the window scan, so the whole ingest->decide->bank loop
costs one device dispatch per batch. Unlike the LM-decides variant of
this example (pre-registry), the rg-LRU policy is per-env row-wise, so
the fused ``_sharded`` compositions work here too — that is exactly what
its certificate proves.

Accessor rules in scan modes: hold pipeline state only through the
donation-safe ``system.snapshot_state()`` / ``snapshot_norm()`` copies,
and read the replay through ``system.export_replay(salt)`` /
``system.replay_size()`` — the device ring stores exact int32 tick
indices (float32 absolute seconds would collapse consecutive window ends
past t~2^24 s), and in the fused-decide modes the ring itself lives in
the DONATED device carry, so ``pred.replay`` is a stale construction-time
snapshot there; the system export snapshots the live carry without
donating it and reconstructs exact float64 absolute times.

Run: PYTHONPATH=src python examples/serve_edge.py \
         [--mode scan|scan_async|scan_sharded|scan_fused_decide|\
          scan_fused_decide_sharded|...|fused]
"""
import argparse
import os
import tempfile
import time

import jax
import numpy as np

from repro import compat
from repro.configs.registry import get_config
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.models import LM
from repro.runtime.db import LogDB
from repro.runtime.forwarder import Forwarder, ForwarderHub
from repro.runtime.predictor import ActionSpace, Predictor
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.system import PerceptaSystem, SourceSpec
from repro.serve.engine import Request, ServeEngine

compat.enable_compile_cache()

# --- the ad-hoc serving model: a real (reduced-config) transformer ---------
cfg_lm = get_config("qwen3-0.6b:smoke")
model = LM(cfg_lm, remat_policy="none")
params = model.init(jax.random.PRNGKey(0))

# --- Percepta wiring ---------------------------------------------------------
ap = argparse.ArgumentParser()
ap.add_argument("--mode", default="scan",
                choices=["scan", "scan_async", "scan_sharded",
                         "scan_async_sharded", "scan_fused_decide",
                         "scan_fused_decide_sharded",
                         "scan_fused_decide_async",
                         "scan_fused_decide_async_sharded", "fused"],
                help="device execution mode; the scan_fused_decide modes "
                     "fuse the policy/reward/replay step into the window "
                     "scan (one dispatch per batch, device-resident replay "
                     "ring + recurrent policy carry). The *_sharded "
                     "compositions split envs over the device mesh — "
                     "admissible because the registry rg-LRU policy is "
                     "certified per-env row-wise at registration")
args = ap.parse_args()
SCAN_K = 2  # windows per scan-fused dispatch
E = 4
sources = [
    SourceSpec("meter", "mqtt", SimulatedDevice("grid_kw", 60.0, base=3.0,
                                                seed=1)),
    SourceSpec("price", "http", SimulatedDevice("price_eur", 300.0, base=0.2,
                                                amplitude=0.05, seed=2)),
    SourceSpec("thermo", "amqp", SimulatedDevice("temp_c", 30.0, base=21.0,
                                                 amplitude=1.5, seed=3)),
]
pcfg = PipelineConfig(n_envs=E, n_streams=3, n_ticks=8, tick_s=60.0,
                      max_samples=32)
# the registry policy: Predictor accepts the registry NAME (or a
# PolicyConfig) and resolves it at its own (n_features, n_actions, n_envs)
# — build_policy certifies the builder before the adapter is returned, and
# the certificate travels on the model for the system's fused-mode gate
pred = Predictor("rglru",
                 energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=2),
                 ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                 E, pcfg.n_features, db=None, replay_capacity=256)
print(f"decision policy: {pred.model.certificate.describe()}")
db = LogDB(os.path.join(tempfile.gettempdir(), "percepta_serve_db"),
           salt="opeva")
hub = ForwarderHub([Forwarder("hvac", "mqtt", [0]),
                    Forwarder("ev-charger", "amqp", [1])])
system = PerceptaSystem([f"bldg-{i}" for i in range(E)], sources, pcfg, pred,
                        forwarders=hub, db=db, speedup=4000.0,
                        mode=args.mode, scan_k=SCAN_K)

# --- ad-hoc batched request serving between ticks ---------------------------
engine = ServeEngine(model, params, batch_slots=4, max_seq=64)
rng = np.random.RandomState(0)

batch = 1 if args.mode == "fused" else SCAN_K
print(f"=== Percepta edge serving: 6 windows ({args.mode} mode, "
      f"{batch} windows/dispatch), 12 ad-hoc requests ===")
t_start = time.time()
tok_count = 0
for w in range(0, 6, batch):
    results = system.run_windows(batch)
    # serve batched ad-hoc requests while streams accumulate (2 per window
    # regardless of dispatch batching, so both modes serve 12 total)
    reqs = [Request(rid=w * 10 + j,
                    prompt=rng.randint(1, cfg_lm.vocab_size, (6,))
                    .astype(np.int32), max_new_tokens=8)
            for j in range(2 * batch)]
    engine.run_until_drained(reqs)
    tok_count += sum(len(q.tokens) for q in reqs)
    for r in results:
        print(f"window {r['window']}: {r['records']:4d} records  "
              f"tick {r['latency_s']*1e3:6.1f} ms  "
              f"reward {r['mean_reward']:+.3f}  "
              f"observed {r['observed_frac']:.0%}  "
              f"filled {r['filled_frac']:.0%}")

dt = time.time() - t_start
print(f"\nforwarded decisions: "
      f"{ {f.dest_id: f.stats['sent'] for f in hub.forwarders} }")
# replay accessor rule: device-side times are exact int32 tick indices;
# the system export re-attaches exact float64 absolute times (host mirror,
# or tick-index reconstruction in fused-decide modes where the ring lives
# in the donated device carry) and rolls the ring chronological — never
# read replay.tick_idx as seconds, never alias pred.replay in fused modes
dataset = system.export_replay(salt="opeva")
print(f"DB rows (anonymized): {db.stats['rows']}  "
      f"replay transitions: {system.replay_size()}  "
      f"export t=[{dataset['times'][0, 0]:.0f}"
      f"..{dataset['times'][0, -1]:.0f}]s")
print(f"ad-hoc serving: {tok_count} tokens via continuous batching "
      f"({engine.stats['ticks']} engine ticks)")
print(f"wall time {dt:.1f}s for 48 stream-minutes x {E} buildings + serving")
db.close()
