"""Whether what the timed path produced is correct: the program's outputs
held to the plain reference (``reference.py``) run over the same windows,
the same batches and the same seed.

Numbers compared (each has a limit in ``bench/limits/<cell>.json``):

* ``count_mismatch`` (exact, limit 0): readings lost on the way
  (accumulator overflow, queue drops, unknown streams), observed and
  filled tick counts per window against the reference, missing or extra
  actions, actions tagged with the wrong window, and the banked rows'
  tick, policy version and validity;
* ``action_gap``: the largest |program - reference| over every forwarded
  action (actions lie in [-1, 1]);
* ``reward_gap``: the largest gap of a window's mean reward, over the mean
  magnitude of the reference's window means;
* ``replay_gap``: per banked column (obs, actions, rewards, next_obs) of a
  sample of environments drawn from the seed, the largest gap over that
  column's largest magnitude; the worst column;
* ``policy_gap``: per weight matrix of the policy that served the last
  batch, ||program - reference|| / ||reference||; the worst matrix.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from reference import Reference, window_inputs

REPLAY_COLUMNS = ("obs", "actions", "rewards", "next_obs")
EXACT_COLUMNS = ("tick_idx", "version", "valid")
NUMBERS = ("count_mismatch", "action_gap", "reward_gap", "replay_gap",
           "policy_gap")


@dataclass
class Outputs:
    """What one side produced over windows ``0 .. n - 1``."""
    observed: np.ndarray         # (n,) observed ticks per window
    filled: np.ndarray           # (n,) filled ticks per window
    mean_reward: np.ndarray      # (n,)
    actions: np.ndarray          # (n, E, A) as forwarded
    bad_tick_times: int          # actions tagged with another window
    replay: Dict[str, np.ndarray]  # columns (env sample, slots[, ...])
    served_policy: Dict[str, np.ndarray]
    lost: int = 0                # overflow + queue drops + unknown streams
    anomalous: Optional[np.ndarray] = None
    extra: dict = field(default_factory=dict)


def env_sample(n_envs: int, seed_word: int, size: int = 256) -> np.ndarray:
    rng = np.random.default_rng(seed_word)
    return np.sort(rng.choice(n_envs, min(n_envs, size), replace=False))


def program_outputs(system, sink, rows: List[dict], envs: np.ndarray,
                    window_s: float, t0: float = 0.0) -> Outputs:
    """Collect the program's outputs: rows, forwarded actions, a slice of
    the replay ring (the sampled envs, the written slots) and the policy
    that served the last batch."""
    cfg = system.cfg
    E, S, T = cfg.n_envs, cfg.n_streams, cfg.n_ticks
    A = system.predictor.action_space.n
    n = len(rows)
    denom = E * S * T
    t, v = sink.decoded()
    if v.size == n * E * A:
        actions = v.reshape(n, E, A)
        ends = t0 + (np.arange(n) + 1) * window_s
        bad = int((t.reshape(n, E * A) != ends[:, None]).sum())
    else:
        actions = np.full((n, E, A), np.nan)
        bad = abs(v.size - n * E * A)
    buf = system._dstate.replay        # read in place: no copy of the ring
    size = min(int(buf.cursor), buf.capacity)
    # a plain slice of the written slots, then the sampled envs on the
    # host: a gather on the device would lay the whole ring out anew (a
    # ring of 9.8 GB then needs 18 GB)
    replay = {c: np.asarray(getattr(buf, c)[:, :size])[np.asarray(envs)]
              for c in REPLAY_COLUMNS + EXACT_COLUMNS}
    accs = system.accumulators.values()
    lost = sum(a.stats["overflow"] + a.stats["unknown_stream"] for a in accs)
    lost += sum(q["dropped"] for q in system.broker.stats().values())
    return Outputs(
        observed=np.rint(np.asarray([r["observed_frac"] for r in rows])
                         * denom).astype(np.int64),
        filled=np.rint(np.asarray([r["filled_frac"] for r in rows])
                       * denom).astype(np.int64),
        mean_reward=np.asarray([r["mean_reward"] for r in rows]),
        actions=actions, bad_tick_times=bad, replay=replay,
        served_policy={k: np.asarray(x)
                       for k, x in system.snapshot_policy().items()},
        lost=int(lost),
        anomalous=np.asarray([r["anomalous"] for r in rows], np.int64),
        extra={"train": system.train_stats()})


class PoolInputs:
    """Per-window (E, S, T) sums and counts of the pool, made once per pool
    entry (window ``w`` uses entry ``w % pool``)."""

    def __init__(self, pool, quantize=None):
        self.pool = pool
        self.quantize = quantize
        self._cache = {}

    def __call__(self, w: int):
        p = w % self.pool.P
        if p not in self._cache:
            cfg = self.pool.cfg
            kw = {} if self.quantize is None else {"quantize": self.quantize}
            parts = [window_inputs(b.ts, b.values, b.offsets, self.pool.E,
                                   float(cfg["tick_s"]), int(cfg["n_ticks"]),
                                   **kw)
                     for b in self.pool.blocks[p]]
            sums = np.stack([s for s, _ in parts], axis=1)
            counts = np.stack([c for _, c in parts], axis=1)
            self._cache[p] = (sums, counts)
        return self._cache[p]


def reference_outputs(cfg: dict, pool, ks: List[int], envs: np.ndarray,
                      policy_seed: int, trainer_seed: int,
                      quantize=None) -> Outputs:
    """Run the reference (or, with ``quantize``, the control) over the
    windows the program ran, batch by batch as the program was driven."""
    ref = Reference(cfg, policy_seed, trainer_seed, quantize=quantize)
    inputs = PoolInputs(pool, quantize)
    n = int(sum(ks))
    E, A = ref.E, ref.A
    observed, filled, anomalous = (np.zeros(n, np.int64) for _ in range(3))
    mean_reward = np.zeros(n)
    actions = np.zeros((n, E, A))
    served = None
    w = 0
    for k in ks:
        served = {key: ref.params[key].copy() for key in ref.params}
        for _ in range(k):
            out = ref.window(*inputs(w))
            observed[w], filled[w] = out["observed"], out["filled"]
            anomalous[w] = out["anomalous"]
            mean_reward[w] = float(np.mean(out["rewards"]))
            actions[w] = out["actions"]
            w += 1
        ref.train()
    ref.close()
    size = min(len(ref.ring["rewards"]), ref.capacity)
    index = ref.slot_index(np.arange(size))
    replay = {c: np.stack([np.asarray(ref.ring[c][i])[envs] for i in index],
                          axis=1)
              for c in REPLAY_COLUMNS}
    replay["tick_idx"] = np.broadcast_to(index + 1, (len(envs), size))
    replay["version"] = np.broadcast_to(
        np.asarray(ref.ring["version"])[index], (len(envs), size))
    replay["valid"] = np.ones((len(envs), size), bool)
    return Outputs(observed, filled, mean_reward, actions, 0,
                   replay, served or {}, 0, anomalous,
                   extra={"losses": ref.losses})


def _max_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    d = np.abs(a - b)
    return float("inf") if not np.all(np.isfinite(d)) else float(d.max())


def compare(prog: Outputs, ref: Outputs) -> Dict[str, float]:
    """The numbers compared, program (or control) against the reference."""
    n = min(len(prog.observed), len(ref.observed))
    counts = abs(len(prog.observed) - len(ref.observed)) * 1000
    counts += prog.lost + prog.bad_tick_times
    counts += int(np.abs(prog.observed[:n] - ref.observed[:n]).sum())
    counts += int(np.abs(prog.filled[:n] - ref.filled[:n]).sum())
    for c in EXACT_COLUMNS:
        p, r = prog.replay.get(c), ref.replay.get(c)
        if p is None or r is None or p.shape != r.shape:
            counts += 1000
        else:
            counts += int((np.asarray(p) != np.asarray(r)).sum())
    action_gap = _max_gap(prog.actions[:n], ref.actions[:n])
    scale = float(np.mean(np.abs(ref.mean_reward[:n]))) or 1.0
    reward_gap = _max_gap(prog.mean_reward[:n], ref.mean_reward[:n]) / scale
    replay_gap = 0.0
    for c in REPLAY_COLUMNS:
        p, r = prog.replay.get(c), ref.replay.get(c)
        if p is None or r is None or p.shape != r.shape:
            replay_gap = float("inf")
            continue
        top = float(np.max(np.abs(r))) if r.size else 0.0
        replay_gap = max(replay_gap, _max_gap(p, r) / (top or 1.0))
    policy_gap = 0.0
    for key, r in ref.served_policy.items():
        p = prog.served_policy.get(key)
        if p is None or np.shape(p) != np.shape(r):
            policy_gap = float("inf")
            continue
        d = np.linalg.norm(np.asarray(p, np.float64) - r)
        policy_gap = max(policy_gap, float(d / (np.linalg.norm(r) or 1.0))
                         if np.isfinite(d) else float("inf"))
    return {"count_mismatch": float(counts), "action_gap": action_gap,
            "reward_gap": reward_gap, "replay_gap": replay_gap,
            "policy_gap": policy_gap}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= float(limits[k]) for k in NUMBERS)
