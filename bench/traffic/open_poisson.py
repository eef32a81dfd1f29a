"""Open loop on the wall clock: independent devices, each a Poisson stream
with bursts, posting on a schedule that does not slow when the engine does.

The device model is the sensor-trace model of ``repro.workloads.traces``
(copied here so the yardstick does not move with the program), moved from
rounds to seconds:

* each device's rate follows a diurnal envelope ``1 + amp*sin(2*pi*phase)``
  with a per-device phase drawn uniformly.  A day is far longer than a
  run, so within a run each device keeps the rate of its phase, and the
  staggered phases make the fleet's total flat: the rates are scaled so
  that they sum to ``rate_per_s`` exactly, whatever the seed;
* after each arrival a quiet device starts a burst with ``burst_prob``;
  during a burst its next ``burst_len`` inter-arrival gaps shrink by
  ``burst_boost`` (the device's own time, not the wall clock's);
* each arrival's reading follows a clamped Gaussian random walk.

Scheduled times are whole microseconds, strictly increasing per device;
they are both the event timestamp and its creation stamp.
"""
from __future__ import annotations

import numpy as np


class OpenPoisson:
    closed = False

    def __init__(self, params: dict, n_devices: int, seconds: float,
                 seed: int):
        p = params
        self.tail_s = float(p.get("tail_s", 1.0))
        horizon_us = (float(seconds) + self.tail_s) * 1e6
        rng = np.random.default_rng(seed)
        n = int(n_devices)
        phase = rng.uniform(0.0, 1.0, n)
        env = 1.0 + float(p["diurnal_amp"]) * np.sin(2.0 * np.pi * phase)
        rate = float(p["rate_per_s"]) * env / env.sum()      # events/s
        mean_gap_us = 1e6 / rate
        lo, hi = float(p["value_lo"]), float(p["value_hi"])
        value = rng.uniform(lo, hi, n)
        boost = float(p["burst_boost"])
        burst_len = int(p["burst_len"])
        burst_prob = float(p["burst_prob"])
        sigma = float(p["walk_sigma"])

        t = np.zeros(n)
        last = np.full(n, -1, np.int64)
        burst_left = np.zeros(n, np.int64)
        live = np.ones(n, bool)
        ts, dev, vals = [], [], []
        while live.any():
            gap = rng.exponential(1.0, n) * mean_gap_us
            t = t + np.where(burst_left > 0, gap / boost, gap)
            start = rng.random(n) < burst_prob
            burst_left = np.where((burst_left == 0) & start, burst_len,
                                  np.maximum(burst_left - 1, 0))
            value = np.clip(value + rng.normal(0.0, sigma, n), lo, hi)
            live = t < horizon_us
            stamp = np.maximum(np.floor(t).astype(np.int64), last + 1)
            last = np.where(live, stamp, last)
            idx = np.nonzero(live)[0]
            ts.append(stamp[idx])
            dev.append(idx)
            vals.append(value[idx].astype(np.float32))
        ts = np.concatenate(ts)
        order = np.argsort(ts, kind="stable")
        self.sched_us = ts[order]
        self.dev = np.concatenate(dev)[order].astype(np.int64)
        self.vals = np.concatenate(vals)[order]
        self._next = 0

    def due(self, now_us: float):
        """Every not yet posted event scheduled at or before ``now_us``."""
        end = int(np.searchsorted(self.sched_us, now_us, side="right"))
        lo, self._next = self._next, max(end, self._next)
        return (self.dev[lo:self._next], self.vals[lo:self._next],
                self.sched_us[lo:self._next])

    def ts(self, sched_us: np.ndarray, step: int) -> np.ndarray:
        return sched_us


def make(params: dict, n_devices: int, seconds: float, seed: int):
    return OpenPoisson(params, n_devices, seconds, seed)
