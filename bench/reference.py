"""Plain reference of a cell: the same semantics, written independently.

NumPy in float64, one window at a time, over every environment. Nothing
here imports the program or reads what it made: readings come from the
benchmark's own generator, and the policy weights and the trainer's
sampling keys are drawn again from the seed with ``jax.random`` on the
host CPU, by the recipe the configuration names (a SwiGLU MLP drawn from
``PRNGKey(policy_seed)``; the trainer's key chain from
``PRNGKey(trainer_seed)``).

Semantics, per environment ``e``, stream ``s``, window ``w`` of length
``T * tick_s``:

* harmonize: a reading at ``r`` seconds after the window start falls in
  tick ``ceil(r / tick_s) - 1``; each tick holds the mean of its readings
  (values as float32 readings);
* anomaly: once a stream has seen more than 8 observed ticks, a tick more
  than ``k_sigma`` running standard deviations from the running mean is a
  spike and is clipped to that envelope; the running mean and variance
  follow the window's clean ticks with weight 0.05;
* gap fill: last observation carried forward, across windows too;
* normalize: running count, mean and squared deviations over the filled
  ticks (Chan's merge), z-scores with the updated statistics;
* features: each stream's value at the last tick (``per_tick_features``:
  every tick, stream-major); the reward reads the same in engineering
  units;
* decide: ``tanh((silu(x W3) * (x W1)) W2)`` scaled to the action range,
  clipped to it; reward ``-price * max(grid, 0) - 2 max(|temp - target| -
  band, 0) - 0.1 (a_hvac - prev a_hvac)^2``;
* bank: window ``w >= 1`` banks ``(x_{w-1}, a_{w-1}, r_w, x_w)`` at ring
  slot ``w - 1``;
* train: after every dispatch, one AdamW step (lr 3e-4, cosine over 1000
  steps to a tenth, betas 0.9/0.95, eps 1e-8, global-norm clip 1) on a
  uniform minibatch of the ring, loss ``mean((Q(o, a) - r)^2) - 0.1
  mean(Q(o, pi(o)))`` with a linear critic ``Q = [o; a] . qw + qb`` that
  starts at zero; the new weights serve from the next dispatch on.

``quantize`` rounds every value and intermediate, though no timestamp:
``None`` keeps float64; the control passes a rounding to bfloat16.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional

import numpy as np

ALPHA = 0.05            # anomaly statistics' weight of a new window
WARM_TICKS = 8          # observed ticks before spikes are detected
PI_COEF = 0.1           # weight of the policy term of the loss
LR, TOTAL_STEPS, B1, B2, EPS, CLIP = 3e-4, 1000, 0.9, 0.95, 1e-8, 1.0
THREADS = 8             # blocks of at least 128 env rows run in parallel


def _identity(x):
    return x


def bfloat16_round(x):
    """Round to bfloat16 and back: the control's precision."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def window_inputs(block_ts, block_values, offsets, n_envs, tick_s, n_ticks,
                  quantize=_identity):
    """Per (env, tick) sum and count of one stream's readings of a window
    (timestamps in seconds from the window start). ``quantize`` rounds the
    values only: a timestamp is an exact input in every precision, so each
    reading keeps its tick."""
    rel = np.asarray(block_ts, np.float64)
    tick = np.ceil(rel / tick_s).astype(np.int64) - 1
    env = np.repeat(np.arange(n_envs), np.diff(offsets))
    ok = (tick >= 0) & (tick < n_ticks)
    flat = env[ok] * n_ticks + tick[ok]
    vals = quantize(np.asarray(block_values, np.float32).astype(np.float64))
    sums = np.bincount(flat, weights=vals[ok], minlength=n_envs * n_ticks)
    counts = np.bincount(flat, minlength=n_envs * n_ticks)
    return (sums.reshape(n_envs, n_ticks), counts.reshape(n_envs, n_ticks))


def policy_weights(n_features, n_actions, hidden, policy_seed):
    """The SwiGLU MLP's weights, drawn on the host CPU from the seed."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(policy_seed), 3)
        w1 = jax.random.normal(k1, (n_features, hidden)) / math.sqrt(
            n_features)
        w3 = jax.random.normal(k2, (n_features, hidden)) / math.sqrt(
            n_features)
        w2 = jax.random.normal(k3, (hidden, n_actions)) / math.sqrt(hidden)
        return {k: np.asarray(v, np.float64)
                for k, v in (("w1", w1), ("w2", w2), ("w3", w3))}


class TrainerKeys:
    """The trainer's key chain: ``rng, sub = split(rng)`` per dispatch, and
    a minibatch of ``batch`` (env, slot) draws from each ``sub``."""

    def __init__(self, trainer_seed: int):
        import jax
        self._jax = jax
        self._cpu = jax.devices("cpu")[0]
        with jax.default_device(self._cpu):
            self.rng = jax.random.PRNGKey(trainer_seed)

    def draw(self, batch: int, n_envs: int, size: int):
        jax = self._jax
        with jax.default_device(self._cpu):
            self.rng, sub = jax.random.split(self.rng)
            ke, ks = jax.random.split(sub)
            es = jax.random.randint(ke, (batch,), 0, n_envs)
            ss = jax.random.randint(ks, (batch,), 0, max(size, 1))
            return np.asarray(es), np.asarray(ss)


def _sigmoid(x):
    return 0.5 * (1.0 + np.tanh(0.5 * x))


class Reference:
    """The reference system of one cell, stepped window by window."""

    def __init__(self, cfg: dict, policy_seed: int, trainer_seed: int,
                 quantize: Optional[Callable] = None):
        self.q = quantize or _identity
        self._executor = None
        q = self.q
        self.cfg = cfg
        E, S, T = cfg["n_envs"], len(cfg["streams"]), cfg["n_ticks"]
        self.E, self.S, self.T = E, S, T
        self.per_tick = bool(cfg["per_tick_features"])
        self.F = S * T if self.per_tick else S
        self.A = int(cfg["n_actions"])
        self.low, self.high = float(cfg["action_low"]), float(cfg["action_high"])
        self.k_sigma = float(cfg["k_sigma"])
        z = np.zeros((E, S))
        self.an_mean, self.an_var, self.an_count = z.copy(), z + 1.0, z.copy()
        self.gf_value, self.gf_has = z.copy(), np.zeros((E, S), bool)
        self.nz_count, self.nz_mean, self.nz_m2 = z.copy(), z.copy(), z.copy()
        self.prev_x = np.zeros((E, self.F))
        self.prev_a = np.zeros((E, self.A))
        self.have_prev = False
        self.params = {k: q(v) for k, v in policy_weights(
            self.F, self.A, int(cfg["policy_hidden"]), policy_seed).items()}
        self.critic = {"qb": 0.0, "qw": np.zeros(self.F + self.A)}
        self.opt_m = {k: np.zeros_like(v) for k, v in self._joint().items()}
        self.opt_v = {k: np.zeros_like(v) for k, v in self._joint().items()}
        self.opt_step = 0
        self.keys = TrainerKeys(trainer_seed)
        self.capacity = int(cfg["replay_capacity"])
        self.batch = int(cfg["train_batch"])
        # every banked transition, in order (transition i = window i + 1);
        # ring slot s holds the newest i with i % capacity == s
        self.ring = {"obs": [], "actions": [], "rewards": [], "next_obs": [],
                     "version": []}
        self.version = 0
        self.version_of_prev = 0
        self.windows = 0
        self.losses: List[float] = []

    # --- the window body ----------------------------------------------------
    def window(self, sums, counts):
        """One window over every env; ``sums``/``counts`` (E, S, T).
        Returns the window's outputs as a dict. Env rows are independent,
        so blocks of rows run on a few threads (NumPy releases the
        interpreter lock inside its loops)."""
        E = self.E
        step = -(-E // max(1, min(THREADS, E // 128)))
        blocks = [slice(i, min(E, i + step)) for i in range(0, E, step)]
        if len(blocks) > 1:
            parts = list(self._pool().map(
                lambda sl: self._rows(sl, sums[sl], counts[sl]), blocks))
        else:
            parts = [self._rows(blocks[0], sums, counts)]
        x = np.concatenate([p["features"] for p in parts])
        a = np.concatenate([p["actions"] for p in parts])
        r = np.concatenate([p["rewards"] for p in parts])
        # bank
        if self.have_prev:
            self.ring["obs"].append(self.prev_x)
            self.ring["actions"].append(self.prev_a)
            self.ring["rewards"].append(r)
            self.ring["next_obs"].append(x)
            self.ring["version"].append(self.version_of_prev)
        self.prev_x, self.prev_a, self.have_prev = x, a, True
        self.version_of_prev = self.version
        self.windows += 1
        return {"features": x, "actions": a, "rewards": r,
                "observed": sum(p["observed"] for p in parts),
                "filled": sum(p["filled"] for p in parts),
                "anomalous": sum(p["anomalous"] for p in parts)}

    def _pool(self):
        if self._executor is None:
            from concurrent.futures import ThreadPoolExecutor
            self._executor = ThreadPoolExecutor(THREADS)
        return self._executor

    def close(self):
        if self._executor is not None:
            self._executor.shutdown()
            self._executor = None

    def _rows(self, sl, sums, counts):
        """The window body and the decision for env rows ``sl``."""
        q, k = self.q, self.k_sigma
        obs = counts > 0
        v = q(np.where(obs, sums / np.maximum(counts, 1), 0.0))
        # anomaly: detect against the running statistics, clip, update
        an_mean, an_var, an_count = (self.an_mean[sl], self.an_var[sl],
                                     self.an_count[sl])
        sd = q(np.sqrt(np.maximum(an_var, 1e-12)))[..., None]
        mu = an_mean[..., None]
        warm = (an_count > WARM_TICKS)[..., None]
        spikes = obs & warm & (q(np.abs(v - mu) / sd) > k)
        v = q(np.where(spikes, np.clip(v, mu - k * sd, mu + k * sd), v))
        n = obs.sum(-1)
        mean_w = q(np.where(obs, v, 0.0).sum(-1) / np.maximum(n, 1))
        var_w = q((np.where(obs, (v - mean_w[..., None]) ** 2, 0.0)).sum(-1)
                  / np.maximum(n, 1))
        boot = an_count < 1
        new_mean = np.where(boot, mean_w, (1 - ALPHA) * an_mean
                            + ALPHA * mean_w)
        new_var = np.where(boot, np.maximum(var_w, 1e-6),
                           (1 - ALPHA) * an_var
                           + ALPHA * (var_w + (mean_w - an_mean) ** 2))
        has = n > 0
        self.an_mean[sl] = q(np.where(has, new_mean, an_mean))
        self.an_var[sl] = q(np.where(has, new_var, an_var))
        self.an_count[sl] = an_count + n
        # gap fill: last observation carried forward
        t_idx = np.arange(self.T)
        last_obs = np.maximum.accumulate(np.where(obs, t_idx, -1), axis=-1)
        carried = np.take_along_axis(v, np.maximum(last_obs, 0), axis=-1)
        carried = np.where(last_obs >= 0, carried,
                           self.gf_value[sl][..., None])
        have = (last_obs >= 0) | self.gf_has[sl][..., None]
        filled = ~obs & have
        v = q(np.where(obs, v, np.where(filled, carried, 0.0)))
        any_obs = obs.any(-1)
        self.gf_value[sl] = np.where(any_obs, carried[..., -1],
                                     self.gf_value[sl])
        self.gf_has[sl] = self.gf_has[sl] | any_obs
        # normalize: Chan's merge of the window into the running stats
        m = obs | filled
        nb = m.sum(-1)
        mb = q(np.where(m, v, 0.0).sum(-1) / np.maximum(nb, 1))
        m2b = q(np.where(m, (v - mb[..., None]) ** 2, 0.0).sum(-1))
        na, mean0, m20 = self.nz_count[sl], self.nz_mean[sl], self.nz_m2[sl]
        nn = na + nb
        delta = mb - mean0
        mean = q(np.where(nn > 0, mean0 + delta * nb / np.maximum(nn, 1),
                          mean0))
        m2 = q(np.where(nb > 0, m20 + m2b
                        + delta ** 2 * na * nb / np.maximum(nn, 1), m20))
        self.nz_mean[sl], self.nz_m2[sl], self.nz_count[sl] = mean, m2, nn
        sigma = q(np.sqrt(np.maximum(m2 / np.maximum(nn - 1, 1), 1e-12)))
        zs = q((v - mean[..., None]) / np.maximum(sigma, 1e-6)[..., None])
        rows = v.shape[0]
        if self.per_tick:
            x, raw = zs.reshape(rows, -1), v.reshape(rows, -1)
        else:
            x, raw = zs[..., -1], v[..., -1]
        # decide
        a = np.clip(self.policy(self.params, x), self.low, self.high)
        r = self.reward(raw, a, self.prev_a[sl])
        return {"features": x, "actions": a, "rewards": r,
                "observed": int(obs.sum()), "filled": int(filled.sum()),
                "anomalous": int(spikes.sum())}

    def policy(self, p, x):
        q = self.q
        h = q(x @ p["w1"])
        g = q(x @ p["w3"])
        y = q((q(g * _sigmoid(g)) * h) @ p["w2"])
        scale, mid = (self.high - self.low) / 2, (self.high + self.low) / 2
        return q(np.tanh(y) * scale + mid)

    def reward(self, raw, a, prev_a):
        rw, q = self.cfg["reward"], self.q
        cost = -raw[:, rw["price_idx"]] * np.maximum(raw[:, rw["grid_idx"]],
                                                     0.0)
        band = -2.0 * np.maximum(np.abs(raw[:, rw["temp_idx"]]
                                        - rw["comfort_target"])
                                 - rw["comfort_band"], 0.0)
        h = rw["hvac_action"]
        smooth = -0.1 * (a[:, h] - prev_a[:, h]) ** 2
        return q(q(q(cost) + q(band)) + q(smooth))

    # --- the online train step ----------------------------------------------
    def _joint(self):
        return {"w1": self.params["w1"], "w2": self.params["w2"],
                "w3": self.params["w3"], "qw": self.critic["qw"],
                "qb": np.asarray(self.critic["qb"], np.float64)}

    def slot_index(self, slots):
        """Transition index held by each ring slot."""
        n, C = len(self.ring["rewards"]), self.capacity
        slots = np.asarray(slots)
        return slots + C * ((n - 1 - slots) // C)

    def train(self):
        """One train step after a dispatch (a no-op on an empty ring)."""
        q = self.q
        size = min(len(self.ring["rewards"]), self.capacity)
        es, ss = self.keys.draw(self.batch, self.E, size)
        if size == 0:
            return
        ss = self.slot_index(ss)
        o = np.stack([self.ring["obs"][s][e] for e, s in zip(es, ss)])
        act = np.stack([self.ring["actions"][s][e] for e, s in zip(es, ss)])
        rew = np.asarray([self.ring["rewards"][s][e] for e, s in zip(es, ss)])
        p, qw, qb = self.params, self.critic["qw"], float(self.critic["qb"])
        nv = float(len(es))
        F = self.F
        # forward
        qb_ = q(o @ qw[:F] + act @ qw[F:] + qb)
        h, g = q(o @ p["w1"]), q(o @ p["w3"])
        sg = _sigmoid(g)
        s_ = q(g * sg)
        pre = q(s_ * h)
        y = q(pre @ p["w2"])
        scale = (self.high - self.low) / 2
        a_pi = q(np.tanh(y) * scale + (self.high + self.low) / 2)
        q_pi = q(o @ qw[:F] + a_pi @ qw[F:] + qb)
        loss = (np.sum((qb_ - rew) ** 2) / nv
                - PI_COEF * np.sum(q_pi) / nv)
        self.losses.append(float(loss))
        # backward
        d_qb = q(2.0 * (qb_ - rew) / nv)                # (B,)
        d_qpi = np.full(len(es), -PI_COEF / nv)
        g_qw = q(np.concatenate([o.T @ d_qb + o.T @ d_qpi,
                                 act.T @ d_qb + a_pi.T @ d_qpi]))
        g_qb = float(d_qb.sum() + d_qpi.sum())
        d_api = q(d_qpi[:, None] * qw[F:][None, :])      # (B, A)
        d_y = q(d_api * scale * (1.0 - np.tanh(y) ** 2))
        g_w2 = q(pre.T @ d_y)
        d_pre = q(d_y @ p["w2"].T)
        d_h = q(d_pre * s_)
        d_s = q(d_pre * h)
        d_g = q(d_s * (sg * (1.0 + g * (1.0 - sg))))
        grads = {"w1": q(o.T @ d_h), "w2": g_w2, "w3": q(o.T @ d_g),
                 "qw": g_qw, "qb": np.asarray(g_qb)}
        # AdamW (no weight decay), global-norm clip over the joint tree
        norm = math.sqrt(sum(float(np.sum(np.square(g)))
                             for g in grads.values()))
        clip = min(1.0, CLIP / max(norm, 1e-12))
        self.opt_step += 1
        t = min(max(self.opt_step / TOTAL_STEPS, 0.0), 1.0)
        lr = LR * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * t)))
        c1, c2 = 1.0 - B1 ** self.opt_step, 1.0 - B2 ** self.opt_step
        joint = self._joint()
        new = {}
        for k, g in grads.items():
            g = g * clip
            self.opt_m[k] = q(B1 * self.opt_m[k] + (1 - B1) * g)
            self.opt_v[k] = q(B2 * self.opt_v[k] + (1 - B2) * g * g)
            step = (self.opt_m[k] / c1) / (np.sqrt(self.opt_v[k] / c2) + EPS)
            new[k] = q(joint[k] - lr * step)
        self.params = {k: new[k] for k in ("w1", "w2", "w3")}
        self.critic = {"qw": new["qw"], "qb": float(new["qb"])}
        self.version += 1
