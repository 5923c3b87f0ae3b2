"""Cloud deployment: one Percepta instance serving MANY isolated
environments simultaneously (paper §III.B/C) — scaling sweep with per-env
latency, demonstrating that environments are rows of one SPMD tick.

Run: PYTHONPATH=src python examples/multi_env_cloud.py
"""
import time

import numpy as np

from repro import compat
from repro.core import PipelineConfig
from repro.core.reward import energy_reward_spec
from repro.runtime.predictor import ActionSpace, Predictor, linear_policy
from repro.runtime.receivers import SimulatedDevice
from repro.runtime.system import PerceptaSystem, SourceSpec

compat.enable_compile_cache()

print("=== Percepta cloud mode: environment-count scaling ===")
print(f"{'envs':>6s} {'tick ms':>9s} {'us/env':>8s} {'env-ticks/s':>12s}")

for E in (1, 8, 64, 256):  # add 1024+ on a real host (1-core CI budget here)
    sources = [
        SourceSpec("meter", "mqtt", SimulatedDevice("grid_kw", 60.0,
                                                    base=3.0, seed=1)),
        SourceSpec("price", "http", SimulatedDevice("price", 300.0, base=0.2,
                                                    amplitude=0.05, seed=2)),
        SourceSpec("thermo", "amqp", SimulatedDevice("temp_c", 30.0,
                                                     base=21.0, seed=3)),
    ]
    pcfg = PipelineConfig(n_envs=E, n_streams=3, n_ticks=8, tick_s=60.0,
                          max_samples=16)
    pred = Predictor(linear_policy(3, 2),
                     energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=2),
                     ActionSpace(np.array([-1., -1.]), np.array([1., 1.])),
                     E, pcfg.n_features, replay_capacity=8)
    sys_ = PerceptaSystem([f"b{i}" for i in range(E)], sources, pcfg, pred,
                          speedup=50000.0)
    sys_.run_windows(1)            # compile + warm
    res = sys_.run_windows(2)
    lat = np.mean([r["latency_s"] for r in res])
    print(f"{E:6d} {lat*1e3:9.2f} {lat/E*1e6:8.1f} {E/lat:12.0f}")

print("\nisolation: each env keeps its own queue/accumulator/state row;"
      "\nthe batched tick scales sub-linearly in env count (SPMD rows).")
