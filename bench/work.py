"""The work that one window of the window -> decide -> bank path and one
online train step need at a configuration's shapes, counted from the
algorithm and not from any implementation: the bytes it must read and
write at least, and its floating-point operations. A later kernel that
does the same work reads against the same numbers.

Bytes: each reading in (float32 value and timestamp), the per (env,
stream) state read and written (anomaly mean/variance/count, last value
and time, normalizer count/mean/m2), the previous features and actions,
one banked transition per env (features twice, actions, reward, tick,
version, valid) and the outputs (actions, reward, three counts).

Operations: per reading, its tick and its share of the tick sum (4); per
(env, stream, tick), the mean, spike test and clip, the carried value and
the z-score with the running statistics (20); per (env, stream), the
window statistics and their merges (30); the policy's three matrix
products (2 F H twice, 2 H A) and its activations (4 H + 2 A); the reward
(10).
"""
from __future__ import annotations


def readings_per_window(cfg: dict) -> float:
    W = cfg["n_ticks"] * cfg["tick_s"]
    return cfg["n_envs"] * sum(
        (W / st["interval_s"]) * (1.0 - st["dropout_p"])
        for st in cfg["streams"])


def features(cfg: dict) -> int:
    S = len(cfg["streams"])
    return S * cfg["n_ticks"] if cfg["per_tick_features"] else S


def window_work(cfg: dict) -> dict:
    E, S, T = cfg["n_envs"], len(cfg["streams"]), cfg["n_ticks"]
    F, A, H = features(cfg), cfg["n_actions"], cfg["policy_hidden"]
    n = readings_per_window(cfg)
    state = E * S * 8 * 4 * 2
    banked = E * (2 * F + A + 1 + 3) * 4
    bytes_ = n * 8 + state + E * (F + A) * 4 * 2 + banked + E * (A + 4) * 4
    flops = (n * 4 + E * S * T * 20 + E * S * 30
             + E * (4 * F * H + 2 * H * A + 4 * H + 2 * A) + E * 10)
    return {"bytes": float(bytes_), "flops": float(flops)}


def train_step_work(cfg: dict) -> dict:
    """One AdamW step on a minibatch: the policy's forward twice over (the
    banked and the policy's action) and backward, the critic, the update."""
    F, A, H = features(cfg), cfg["n_actions"], cfg["policy_hidden"]
    B = cfg["train_batch"]
    params = 2 * F * H + H * A + F + A + 1
    fwd = 4 * F * H + 2 * H * A
    flops = B * (3 * fwd + 8 * (F + A)) + 12 * params
    bytes_ = B * (2 * F + A + 1) * 4 + params * 4 * 4
    return {"bytes": float(bytes_), "flops": float(flops)}
