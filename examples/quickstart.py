"""Quickstart: Percepta's per-tick pipeline on synthetic heterogeneous
streams — harmonization, anomaly handling, gap filling, normalization,
reward computation — in ~60 lines.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import PerceptaPipeline, PipelineConfig
from repro.core.frame import make_raw_window
from repro.core.reward import RewardSpec, RewardTerm

compat.enable_compile_cache()

E, S, M, T = 4, 3, 48, 16          # envs, streams, raw samples, ticks
cfg = PipelineConfig(n_envs=E, n_streams=S, n_ticks=T, tick_s=60.0,
                     max_samples=M, gap_strategy="locf",
                     anomaly_policy="clip")
pipe = PerceptaPipeline(cfg, mode="fused")
state = pipe.init_state()

rng = np.random.RandomState(0)
reward = RewardSpec((
    RewardTerm("linear", weight=-1.0, feature=0),            # cost of stream0
    RewardTerm("band_penalty", weight=2.0, feature=2, target=21.0, band=1.0),
))

for window in range(5):
    t0 = window * T * 60.0
    # three sources at different rates: 30 s / 120 s / 600 s
    rates = [30.0, 120.0, 600.0]
    vals = np.zeros((E, S, M), np.float32)
    ts = np.zeros((E, S, M), np.float32)
    ok = np.zeros((E, S, M), bool)
    for s, r in enumerate(rates):
        n = min(int(T * 60 / r), M)
        ts[:, s, :n] = t0 + (np.arange(n) + 1) * r + rng.uniform(0, 1, (E, n))
        base = [3.0, 0.2, 21.0][s]
        vals[:, s, :n] = base + rng.normal(0, 0.1 * base, (E, n))
        ok[:, s, :n] = rng.rand(E, n) > 0.15          # 15% loss
    vals[0, 0, 3] += 500.0                            # inject a spike
    raw = make_raw_window(vals, ts, ok)

    state, feats, frame = pipe.run_tick(state, raw,
                                        jnp.full((E,), t0, jnp.float32))
    total, per_term = reward.compute(feats.raw,
                                     jnp.zeros((E, 1), jnp.float32))
    print(f"window {window}: observed {float(np.asarray(frame.observed).mean()):.0%} "
          f"filled {float(np.asarray(frame.filled).mean()):.0%} "
          f"spikes {int(np.asarray(frame.anomalous).sum())} "
          f"reward {np.asarray(total).mean():+.2f}")

print("feature vector (env 0):", np.asarray(feats.features)[0].round(2))
print("raw engineering units  :", np.asarray(feats.raw)[0].round(2))
