"""Closed loop per superstep: each call posts one step of a sensor trace.

Gateways forward one collection cycle of readings, and the next cycle
follows the engine: the harness posts the next cycle once fewer than
one superstep's ingest waits on the host, so the backlog stays bounded
at any rate the engine reaches.  One step is the model of
``repro.workloads.traces.SensorTrace`` (copied here so the yardstick does
not move with the program): a per-device diurnal envelope over
``diurnal_period`` steps with staggered phases, bursts that multiply a
device's rate by ``burst_boost`` for ``burst_len`` steps, and a clamped
Gaussian random walk of the readings.  The event timestamp is the step
number (from 1); the creation stamp is the time the step was posted.
"""
from __future__ import annotations

import numpy as np


class Wave:
    closed = True           # the next cycle follows the engine
    tail_s = 0.0

    def __init__(self, params: dict, n_devices: int, seconds: float,
                 seed: int):
        p = {k: float(v) for k, v in params.items()}
        self.p = p
        n = self.n = int(n_devices)
        self._rng = rng = np.random.default_rng(seed)
        self._phase = rng.uniform(0.0, p["diurnal_period"], n)
        self._values = rng.uniform(p["value_lo"], p["value_hi"], n)
        self._burst_left = np.zeros(n, np.int64)
        self._k = 0

    def _step(self):
        p, rng, n = self.p, self._rng, self.n
        start = rng.random(n) < p["burst_prob"]
        self._burst_left = np.where((self._burst_left == 0) & start,
                                    int(p["burst_len"]),
                                    np.maximum(self._burst_left - 1, 0))
        env = 1.0 + p["diurnal_amp"] * np.sin(
            2.0 * np.pi * (self._k + self._phase) / p["diurnal_period"])
        rate = np.clip(p["base_rate"] * env, 0.0, 1.0)
        rate = np.clip(np.where(self._burst_left > 0,
                                rate * p["burst_boost"], rate), 0.0, 1.0)
        fired = np.nonzero(rng.random(n) < rate)[0]
        self._values = np.clip(
            self._values + rng.normal(0.0, p["walk_sigma"], n),
            p["value_lo"], p["value_hi"])
        self._k += 1
        return fired, self._values[fired].astype(np.float32)

    def due(self, now_us: float):
        dev, vals = self._step()
        return dev, vals, np.full(dev.shape, int(now_us), np.int64)

    def ts(self, sched_us: np.ndarray, step: int) -> np.ndarray:
        return np.full(sched_us.shape, self._k, np.int64)


def make(params: dict, n_devices: int, seconds: float, seed: int):
    return Wave(params, n_devices, seconds, seed)
