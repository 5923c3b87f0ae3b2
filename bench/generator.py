"""Readings for a cell, made from the seed, and their delivery into the system.

One general generator serves every configuration: each stream of the
configuration file reports every ``interval_s`` with a sinusoid of
``period_s`` around ``base``, Gaussian noise, dropouts, spikes of
``spike_scale`` and a timestamp jitter drawn in whole milliseconds from the
``jitter_s`` range. These are the settings of ``SimulatedDevice``, drawn in
bulk with NumPy instead of one ``random.Random`` per reading.

The readings of ``data_pool_windows`` windows are drawn at set-up; window
``w`` delivers pool entry ``w % pool`` moved to its own stream time. The
pool length is a multiple of every stream's period in windows, so the
sinusoid of window ``w`` is the one its own time would give.

A delivery is one window's readings of one stream of one environment, made
as a Receiver's ``on_batch`` makes it: one ``Translator.translate_batch``
and one ``QueueBroker.publish`` call.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np


def seed_words(seed: int, n: int = 4) -> List[int]:
    """``n`` 31-bit integers derived from any non-negative ``seed``."""
    return [int(x) & 0x7FFFFFFF
            for x in np.random.SeedSequence(int(seed)).generate_state(n)]


@dataclass
class StreamBlock:
    """One pool window of one stream, kept readings only, env-major."""
    offsets: np.ndarray   # (E + 1,) row starts into ts/values
    bounds: list          # [(start, end)] per env, as Python ints
    ts: np.ndarray        # (N,) float64 seconds from the window start
    values: np.ndarray    # (N,) float64


class ReadingPool:
    """The readings of a cell: ``data_pool_windows`` windows per stream."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.E = int(cfg["n_envs"])
        self.window_s = float(cfg["n_ticks"]) * float(cfg["tick_s"])
        self.P = int(cfg["data_pool_windows"])
        W = self.window_s
        for st in cfg["streams"]:
            if (self.P * W) % float(st["period_s"]):
                raise ValueError(f"stream {st['name']}: a pool of {self.P} "
                                 f"windows is no whole number of periods")
        root = np.random.SeedSequence(int(seed))
        self.blocks = [[self._draw(root, p, s, st)
                        for s, st in enumerate(cfg["streams"])]
                       for p in range(self.P)]

    def _draw(self, root, p: int, s: int, st: dict) -> StreamBlock:
        E, W = self.E, self.window_s
        iv = float(st["interval_s"])
        rng = np.random.default_rng(
            np.random.SeedSequence(root.entropy, spawn_key=(p, s)))
        nominal = np.arange(0, math.ceil(W / iv)) * iv       # in [0, W)
        n = nominal.size
        lo_ms, hi_ms = (int(round(x * 1000)) for x in st["jitter_s"])
        jitter = rng.integers(lo_ms, hi_ms, (E, n)) / 1000.0
        ts = nominal[None, :] + jitter
        phase = 2 * np.pi * (p * W + nominal) / float(st["period_s"])
        v = (st["base"] + st["amplitude"] * np.sin(phase)[None, :]
             + rng.normal(0.0, st["noise"], (E, n)))
        spikes = rng.random((E, n)) < st["spike_p"]
        v = v + spikes * st["spike_scale"] * rng.choice([-1.0, 1.0], (E, n))
        keep = rng.random((E, n)) >= st["dropout_p"]
        counts = keep.sum(axis=1)
        offsets = np.zeros(E + 1, np.int64)
        np.cumsum(counts, out=offsets[1:])
        bounds = list(zip(offsets[:-1].tolist(), offsets[1:].tolist()))
        return StreamBlock(offsets, bounds, np.ascontiguousarray(ts[keep]),
                           np.ascontiguousarray(v[keep]))

    def block(self, w: int, s: int) -> StreamBlock:
        return self.blocks[w % self.P][s]


class Deliverer:
    """Delivers whole windows into a ``PerceptaSystem`` the way its
    Receivers would, env by env and stream by stream."""

    def __init__(self, system, pool: ReadingPool, t0: float = 0.0):
        self.pool = pool
        self.t0 = float(t0)
        self.env_ids = list(system.env_ids)
        self.broker = system.broker
        # per stream: (stream name, translator) as the system wired them
        self.routes = [(src.device.stream, system.translators[src.source_id])
                       for src in system.sources]

    def deliver(self, w: int) -> None:
        """All deliveries of window ``w``."""
        base = self.t0 + w * self.pool.window_s
        publish = self.broker.publish
        for s, (stream, tr) in enumerate(self.routes):
            b = self.pool.block(w, s)
            ts, vs = b.ts + base, b.values
            for env, (lo, hi) in zip(self.env_ids, b.bounds):
                batch = tr.translate_batch(env, stream, ts[lo:hi], vs[lo:hi],
                                           True)
                if batch is not None:
                    publish(batch)
