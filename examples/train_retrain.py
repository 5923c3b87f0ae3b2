"""Online retraining: the policy learns ON DEVICE while the fused decide
scan serves, with versioned hot-swaps and crash-recovery checkpoints.

Two ways to retrain a running Percepta deployment:

  * EXPORT path (PR 4 era, still available): ``system.export_replay()``
    hands the ring to the host — full (E, C) transfer, numpy/optimizer
    step outside the system, rebuild to redeploy. Right when retraining
    is OFFLINE (nightly jobs, big models, cross-deployment aggregation)
    and the serving process must not spend device time on learning.

  * DEVICE path (this example, ``train="online"``): ``OnlineTrainer``
    jits ``replay.sample_device`` + one AdamW step into a single
    dispatch that it enqueues right BEHIND each fused decide dispatch —
    the update executes in the dispatch bubble while the host consumes,
    touches only ``batch`` sampled rows instead of exporting the ring,
    and hot-swaps the new weights into the decide carry at the next
    batch boundary (never mid-scan). Every decision row is stamped with
    the ``policy_version`` that produced it, so logs and replay stay
    attributable across swaps. Right when adaptation must be continuous
    and the model is small enough that one update fits the bubble
    (``make bench-pr7``: the device step is several times cheaper than
    one export round-trip, and serving throughput stays within ~10%).

Run: PYTHONPATH=src python examples/train_retrain.py [--windows 30]
"""
import argparse
import os
import shutil
import tempfile

import numpy as np

from repro import compat
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.system import PerceptaSystem, SourceSpec

compat.enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--windows", type=int, default=30)
ap.add_argument("--scan-k", type=int, default=5)
args = ap.parse_args()
# the pre-crash half must cover >= 2 batches so at least one train step is
# APPLIED (and hence checkpointed) before the simulated crash
assert args.windows >= 4 * args.scan_k, "--windows must be >= 4 * --scan-k"

CKDIR = os.path.join(tempfile.gettempdir(), "percepta_online_ckpt")
shutil.rmtree(CKDIR, ignore_errors=True)


def build(train=None, train_cfg=None):
    srcs = [SourceSpec("meter", "mqtt",
                       SimulatedDevice("grid_kw", 60.0, base=3.0, seed=1)),
            SourceSpec("price", "http",
                       SimulatedDevice("price_eur", 300.0, base=0.2,
                                       amplitude=0.05, seed=2))]
    cfg = PipelineConfig(n_envs=2, n_streams=2, n_ticks=8, tick_s=60.0,
                         max_samples=32)
    pred = Predictor(linear_policy(2, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     2, cfg.n_features, replay_capacity=64)
    return PerceptaSystem(["bldg-0", "bldg-1"], srcs, cfg, pred,
                          speedup=5000.0, manual_time=True,
                          mode="scan_fused_decide", scan_k=args.scan_k,
                          train=train, train_cfg=train_cfg)


tcfg = {"batch_size": 64, "checkpoint_dir": CKDIR, "checkpoint_every": 1}

print(f"=== serving {args.windows} windows (K={args.scan_k}) with online "
      "retraining overlapped on the decide dispatches ===")
sys1 = build(train="online", train_cfg=tcfg)
half = (args.windows // 2 // args.scan_k) * args.scan_k
sys1.run_windows(half)
st = sys1.train_stats()
print(f"after {half} windows: dispatched {st['dispatched']} train steps, "
      f"applied {st['applied']}, policy_version {sys1.policy_version()}, "
      f"loss {st['last_loss']:.4f}")
w_crash = np.asarray(sys1.snapshot_policy()["w"]).copy()
v_crash = sys1.policy_version()
sys1.stop()
print(f"-- simulated crash at version {v_crash} --")

# restart: a fresh process restores the newest policy+optimizer snapshot,
# keeps serving, and version numbering continues where it left off
sys2 = build(train="online", train_cfg=tcfg)
restored = sys2.restore_training()
assert restored is not None, "no checkpoint found"
step, params, extra = restored
print(f"-- restored applied-step {step}, policy_version "
      f"{extra['policy_version']} --")
assert sys2.policy_version() == v_crash
assert (np.asarray(sys2.snapshot_policy()["w"]) == w_crash).all()

sys2.run_windows(args.windows - half)
st2 = sys2.train_stats()
print(f"after restart: applied {st2['applied']} total, policy_version "
      f"{sys2.policy_version()}, loss {st2['last_loss']:.4f}")
assert sys2.policy_version() > v_crash, "training must continue after resume"

# attribution: the replay ring records which policy produced every action
exp = sys2.export_replay("demo")
versions = np.asarray(exp["version"])[0]
print("replay version column (env 0):", versions)
assert (np.diff(versions) >= 0).all(), "versions must be monotone in time"
sys2.stop()
print("OK: online retraining overlaps serving, survives a crash, and every "
      "logged action is version-attributed.")
