"""Tenant QoS plane — heavy-vs-light adversarial isolation benchmark.

The headline claim of the QoS plane (ISSUE 4): one tenant's burst cannot
starve another.  This benchmark builds a deliberately adversarial
topology — a *heavy* tenant whose posts amplify through a two-hop fan-out
(every source SU re-enqueues ``fan`` work SUs) far beyond the engine's
drain rate, next to a *light* tenant running two tiny one-hop pipelines —
and measures the light tenant's delivered throughput with the QoS knobs
off (all-zero weight/quota tables: the PR 3 engine behavior bit-exactly,
so the off phase doubles as the baseline) and on (ingest quota on the
heavy tenant + fair-pop weights on both):

  * ``light_emitted_per_round``  — the starvation signal.  Off: the heavy
    amplification keeps the queue full, so the light tenant's ingests are
    shed into ``dropped_overflow`` and its throughput collapses.  On: the
    quota caps the heavy tenant's injections at a sustainable rate
    (excess counted in ``dropped_quota``, charged to the heavy tenant)
    and the weighted-fair pop serves the light tenant's queued SUs, so it
    delivers ~its full offered load;
  * ``jain_weighted``            — Jain fairness index over per-tenant
    throughput normalized by weight, J(x) = (Σx)²/(n·Σx²) ∈ (0, 1];
  * ``rounds_per_s``             — off vs on, timed in *interleaved*
    blocks so host drift cancels.  Both phases run the same compiled
    program (QoS knobs are data), so ``overhead_pct`` isolates the cost
    of active shaping and should sit at noise level (contract: ≤ 10%);
  * ``retraces``                 — compiled-step cache growth while
    weights and quotas are edited *live* every round; the contract, as
    everywhere in this repo, is **0** (the benchmark exits non-zero).

Run ``python -m benchmarks.qos [--rounds R] [--fan F] [--shards S]
[--json PATH] [--smoke]``.  ``--smoke`` is the CI mode (tiny topology,
few rounds; throughput numbers are not meaningful but the retrace and
accounting contracts are enforced).  JSON schema: benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/qos.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro.core import EngineConfig, Registry, create_engine  # noqa: E402
from repro.launch.compiles import (compile_count,              # noqa: E402
                                   use_compile_cache)

HEAVY_W, LIGHT_W = 8, 1          # fair-pop weights used in the on phase


def _build(n_heavy_src: int, fan: int, n_shards: int):
    """The adversarial topology: heavy sources each fan out to ``fan``
    first-hop composites, each of which feeds one second-hop composite
    (so every heavy source SU amplifies into 2*fan queued/processed SUs);
    the light tenant runs two 1:1 pipelines."""
    n_nodes = n_heavy_src * (1 + 2 * fan) + 4
    cfg = EngineConfig(
        n_streams=n_nodes, n_tenants=4, batch=16,
        queue=3 * 16,                      # small on purpose: contention
        max_in=2, max_out=max(fan, 2), prog_len=24, n_temps=12,
        n_shards=n_shards,
        exchange_slots=0,                  # never drop at the exchange
    )
    reg = Registry.with_capacity(cfg, max_streams=n_nodes + 8)
    heavy = reg.create_tenant("heavy", quota_streams=10 ** 9)
    light = reg.create_tenant("light", quota_streams=10 ** 9)
    h_srcs = [reg.create_stream(heavy, f"h{i}", ["v"])
              for i in range(n_heavy_src)]
    for i, src in enumerate(h_srcs):
        for j in range(fan):
            l1 = reg.create_composite(heavy, f"a{i}_{j}", ["v"], [src],
                                      {"v": f"in0.v + {j}"})
            reg.create_composite(heavy, f"b{i}_{j}", ["v"], [l1],
                                 {"v": "in0.v * 2"})
    l_srcs = [reg.create_stream(light, f"l{i}", ["v"]) for i in range(2)]
    l_comps = [reg.create_composite(light, f"lc{i}", ["v"], [s],
                                    {"v": "in0.v + 1"})
               for i, s in enumerate(l_srcs)]
    return cfg, reg, heavy, light, h_srcs, l_srcs, l_comps


def _jain(xs) -> float:
    xs = np.asarray(xs, np.float64)
    denom = len(xs) * float((xs ** 2).sum())
    return float(xs.sum()) ** 2 / denom if denom else 0.0


class _Phase:
    """One engine under the adversarial load (QoS knobs off or on), with
    its counter baselines and accumulated timed rounds."""

    def __init__(self, n_heavy_src, fan, n_shards, qos_on: bool):
        _, reg, self.heavy, self.light, self.h_srcs, self.l_srcs, _ = \
            _build(n_heavy_src, fan, n_shards)
        self.eng = create_engine(reg)
        self.qos_on = qos_on
        self.ts = 1000
        self.time = 0.0
        self.rounds = 0
        # warm-up: trace the round and (for the on phase) the knob ops
        self.eng.post(self.h_srcs[0], [0.0], 1)
        self.eng.round()
        if qos_on:
            self.eng.set_weight(self.heavy, HEAVY_W)
            self.eng.set_weight(self.light, LIGHT_W)
            # sustainable heavy injection: 1 source SU amplifies into
            # 2*fan+1 pops, which must fit the pop budget next to the
            # light tenant's load
            self.eng.set_quota(self.heavy, 1, 2)
        for _ in range(8):                 # settle the warm-up backlog
            self.eng.round()
        self.e0 = {k: v.copy() for k, v in self.eng.tenant_counters().items()}
        self.c0 = self.eng.counters()
        self.cache0 = compile_count(self.eng._step)

    def _wave(self):
        for s in self.h_srcs:              # heavy posts first — adversarial
            self.eng.post(s, [float(self.rounds)], self.ts)
        for s in self.l_srcs:
            self.eng.post(s, [float(self.rounds)], self.ts)

    def run_block(self, n: int) -> None:
        """One timed block of ``n`` loaded rounds (blocks of the off and
        on phases are interleaved by the caller so host drift — thermal,
        cache, container scheduling — cancels instead of biasing one
        phase)."""
        t0 = time.perf_counter()
        for _ in range(n):
            self._wave()
            self.eng.round()
            self.ts += 1
            self.rounds += 1
        jax.block_until_ready(self.eng.state.timestamps)
        self.time += time.perf_counter() - t0

    def snapshot(self) -> None:
        """Freeze the measured-window counters (call after the timed
        blocks, before the churn tail, so per-round stats cover exactly
        the timed rounds)."""
        self.e1 = {k: v.copy() for k, v in self.eng.tenant_counters().items()}
        self.c1 = self.eng.counters()

    def churn_knobs(self, n: int) -> None:
        """Live weight/quota edits under traffic (untimed) — the
        zero-retrace contract."""
        for r in range(n):
            self.eng.set_weight(self.heavy, HEAVY_W + (r % 2))
            self.eng.set_quota(self.heavy, 1, 2 + (r % 2))
            self.eng.set_weight(self.light, LIGHT_W + (r % 2))
            self._wave()
            self.eng.round()
            self.ts += 1
        jax.block_until_ready(self.eng.state.timestamps)

    def report(self):
        """Per-tenant delivery/drop stats over the timed window, plus the
        retrace count covering the whole run (churn tail included)."""
        e1, c1 = self.e1, self.c1
        emitted = e1["emitted"] - self.e0["emitted"]
        per_round = emitted.astype(np.float64) / self.rounds
        return {
            "light_emitted_per_round": float(per_round[self.light.tid]),
            "heavy_emitted_per_round": float(per_round[self.heavy.tid]),
            "light_offered_per_round": float(len(self.l_srcs)),
            "jain_weighted": _jain([per_round[self.heavy.tid] / HEAVY_W,
                                    per_round[self.light.tid] / LIGHT_W]),
            "rounds_per_s": self.rounds / self.time,
            "dropped_overflow": int(c1["dropped_overflow"]
                                    - self.c0["dropped_overflow"]),
            "dropped_quota": int(c1["dropped_quota"]
                                 - self.c0["dropped_quota"]),
            "light_dropped_overflow": int(
                (e1["dropped_overflow"]
                 - self.e0["dropped_overflow"])[self.light.tid]),
            "heavy_dropped_quota": int(
                (e1["dropped_quota"]
                 - self.e0["dropped_quota"])[self.heavy.tid]),
            "retraces": compile_count(self.eng._step) - self.cache0,
        }


def bench(rounds: int, n_heavy_src: int, fan: int, n_shards: int):
    """Two identically built engines — QoS knobs off (all-zero tables:
    bit-identical to the pre-QoS/PR 3 engine) and on — measured in
    *interleaved* timing blocks, then put through a live knob-churn tail
    for the zero-retrace contract.  Note both phases execute the same
    compiled program (the QoS arithmetic is always in the step; knobs are
    data), so ``overhead_pct`` is the data-path + host cost of *active*
    shaping and should sit at noise level; the plane's structural cost
    vs the PR 3 step is what `benchmarks/superstep.py` tracks against
    its checked-in baseline."""
    phases = {"qos_off": _Phase(n_heavy_src, fan, n_shards, False),
              "qos_on": _Phase(n_heavy_src, fan, n_shards, True)}
    block = max(rounds // 8, 1)
    while phases["qos_off"].rounds < rounds:
        n = min(block, rounds - phases["qos_off"].rounds)
        for p in phases.values():          # interleave: drift cancels
            p.run_block(n)
    for p in phases.values():
        p.snapshot()
        p.churn_knobs(max(rounds // 4, 2))
    off = phases["qos_off"].report()
    on = phases["qos_on"].report()
    return {
        "config": {"rounds": rounds, "heavy_sources": n_heavy_src,
                   "fan": fan, "n_shards": n_shards,
                   "weights": {"heavy": HEAVY_W, "light": LIGHT_W},
                   "platform": jax.devices()[0].platform},
        "qos_off": off,
        "qos_on": on,
        "light_fair_share_ratio_off":
            off["light_emitted_per_round"] / off["light_offered_per_round"],
        "light_fair_share_ratio_on":
            on["light_emitted_per_round"] / on["light_offered_per_round"],
        "overhead_pct": 100.0 * (1.0 - on["rounds_per_s"]
                                 / off["rounds_per_s"]),
        "retraces": off["retraces"] + on["retraces"],
    }


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=80)
    ap.add_argument("--heavy-sources", type=int, default=8)
    ap.add_argument("--fan", type=int, default=8)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--json", default=None, help="write results as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny topology, few rounds")
    args = ap.parse_args()
    if args.smoke:
        args.rounds, args.heavy_sources, args.fan = 6, 2, 4

    res = bench(args.rounds, args.heavy_sources, args.fan, args.shards)
    off, on = res["qos_off"], res["qos_on"]
    print(f"light tenant   off {off['light_emitted_per_round']:6.2f} "
          f"emissions/round   on {on['light_emitted_per_round']:6.2f} "
          f"(offered {on['light_offered_per_round']:.0f})")
    print(f"fair share     off {res['light_fair_share_ratio_off']:6.2f}"
          f"   on {res['light_fair_share_ratio_on']:6.2f}"
          "   (contract: on >= 0.5)")
    print(f"jain(weighted) off {off['jain_weighted']:6.3f} "
          f"  on {on['jain_weighted']:6.3f}")
    print(f"rounds/s       off {off['rounds_per_s']:8.1f} "
          f"  on {on['rounds_per_s']:8.1f} "
          f"  overhead {res['overhead_pct']:+.1f}%")
    print(f"heavy shed into dropped_quota: {on['heavy_dropped_quota']}"
          f"   light dropped_overflow off/on: "
          f"{off['light_dropped_overflow']}/{on['light_dropped_overflow']}")
    print(f"retraces during live weight/quota edits: {res['retraces']} "
          "(contract: 0)")
    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if res["retraces"]:
        print("WARNING: QoS knob edits caused recompilation",
              file=sys.stderr)
        sys.exit(1)
    if not args.smoke and res["light_fair_share_ratio_on"] < 0.5:
        print("WARNING: light tenant below half its fair share with QoS on",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
