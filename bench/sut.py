"""The system under test, wired from a configuration file through the
program's normal constructors: ``PerceptaSystem`` in
``mode="scan_fused_decide"`` with ``train="online"``, columnar ingest and
a manual clock, one Receiver/Translator per stream, one Forwarder whose
transmit is the benchmark's sink."""
from __future__ import annotations

import time
from array import array

import numpy as np


class ActionSink:
    """The Forwarder's transmit: keeps every payload, and the time at
    which the last action of each environment's window went out."""

    def __init__(self, n_actions: int, clock=time.perf_counter):
        self.n_actions = n_actions
        self.payloads = []
        self.times = array("d")
        self._clock = clock
        self._n = 0

    def transmit(self, payload: bytes) -> None:
        self.payloads.append(payload)
        self._n += 1
        if self._n % self.n_actions == 0:
            self.times.append(self._clock())

    def decoded(self):
        """(tick_time, value) of every payload, in transmit order (amqp
        payloads: a 32-byte name, then two little-endian float64)."""
        if not self.payloads:
            return np.zeros(0), np.zeros(0)
        raw = np.frombuffer(b"".join(self.payloads), np.uint8).reshape(-1, 48)
        t = raw[:, 32:40].copy().view("<f8").ravel()
        v = raw[:, 40:48].copy().view("<f8").ravel()
        return t, v


def build(cfg: dict, policy_seed: int, trainer_seed: int, sink: ActionSink):
    from repro.core import PipelineConfig
    from repro.core.reward import energy_reward_spec
    from repro.runtime.forwarder import Forwarder, ForwarderHub
    from repro.runtime.policies import PolicyConfig
    from repro.runtime.predictor import ActionSpace, Predictor
    from repro.runtime.receivers import SimulatedDevice
    from repro.runtime.system import PerceptaSystem, SourceSpec

    E, A = int(cfg["n_envs"]), int(cfg["n_actions"])
    pcfg = PipelineConfig(
        n_envs=E, n_streams=len(cfg["streams"]), n_ticks=int(cfg["n_ticks"]),
        tick_s=float(cfg["tick_s"]), max_samples=int(cfg["max_samples"]),
        agg=cfg["agg"], gap_strategy=cfg["gap_strategy"],
        anomaly_policy=cfg["anomaly_policy"], k_sigma=float(cfg["k_sigma"]),
        per_tick_features=bool(cfg["per_tick_features"]),
        feature_agg=cfg["feature_agg"])
    # the Receivers' devices only carry each stream's name and interval
    # here: the benchmark delivers the readings itself
    sources = [SourceSpec(st["name"], "amqp",
                          SimulatedDevice(st["name"], st["interval_s"]))
               for st in cfg["streams"]]
    rw = cfg["reward"]
    reward = energy_reward_spec(
        price_idx=rw["price_idx"], grid_idx=rw["grid_idx"],
        temp_idx=rw["temp_idx"], comfort_target=rw["comfort_target"],
        comfort_band=rw["comfort_band"], hvac_action=rw["hvac_action"])
    low = np.full(A, float(cfg["action_low"]))
    high = np.full(A, float(cfg["action_high"]))
    predictor = Predictor(
        PolicyConfig("mlp", {"hidden": int(cfg["policy_hidden"]),
                             "seed": int(policy_seed)}),
        reward, ActionSpace(low, high), E, pcfg.n_features,
        replay_capacity=int(cfg["replay_capacity"]))
    hub = ForwarderHub([Forwarder("actuators", "amqp", range(A),
                                  transmit=sink.transmit)])
    return PerceptaSystem(
        [f"env-{i:05d}" for i in range(E)], sources, pcfg, predictor,
        forwarders=hub, mode="scan_fused_decide", scan_k=int(cfg["scan_k"]),
        manual_time=True, ingest="columnar", train="online",
        train_cfg={"batch_size": int(cfg["train_batch"]),
                   "seed": int(trainer_seed)})
