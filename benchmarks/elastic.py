"""Elastic mesh — autoscaled shard count vs static peak provisioning.

The economic claim of the elastic plane (ISSUE 7): on a bursty tenant
trace, an engine that starts at 1 shard and lets the :class:`Autoscaler`
grow/shrink the mesh with the backlog spends fewer **device-seconds**
(sum over supersteps of ``active_shards x superstep wall time``) than the
same engine statically provisioned at peak shard count — at an equal drop
rate on the identical trace.  The elasticity itself must stay cheap: the
engine caches compiled closures per shard layout, so after a warm pool
walk (one visit to each count the autoscaler can reach) the measured run
compiles NOTHING — resizes re-use the cached programs.

The trace is quiet -> burst -> quiet: deep pipeline chains keep wavefronts
in flight during the burst, so queue occupancy (the autoscaler's leading
signal) genuinely rises, and the quiet tail lets the mesh shrink back.

Measured:

  * ``device_seconds``  elastic vs static — the headline, plus the
    per-phase shard history and scale events;
  * ``drop_rate``       overflow drops / SUs queued, both engines (the
    equal-service guard: elastic may not win by shedding load);
  * ``compiles``        XLA programs built during the measured elastic
    run — must be ZERO (every layout was visited by the warm pool walk,
    so resizes hit the per-engine closure cache);
  * ``resize_ms``       host latency of each live resize (migration +
    re-lower).

Run ``python -m benchmarks.elastic [--supersteps N] [--max-shards S]
[--k K] [--json PATH] [--smoke]``.  ``--smoke`` is the CI mode (short
trace; exits non-zero on extra retraces, unequal drop rates, or elastic
losing on device-seconds).  JSON schema: benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/elastic.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro.core import EngineConfig, Registry, create_engine  # noqa: E402
from repro.launch.autoscale import Autoscaler                 # noqa: E402
from repro.launch.compiles import (compile_count,              # noqa: E402
                                   use_compile_cache)



def _build(n_chains: int, depth: int, n_shards: int):
    """Chained pipelines: every mid-chain emission re-enqueues, so burst
    ingest holds more wavefronts in flight than one shard's round pops."""
    n_nodes = n_chains * (1 + depth) + 2
    cfg = EngineConfig(
        n_streams=n_nodes, n_tenants=4, batch=8, queue=128,
        max_in=2, max_out=4, prog_len=24, n_temps=12, n_shards=n_shards,
        retention_slots=0, dlq_slots=0,
    )
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t", quota_streams=10 ** 9)
    srcs = [reg.create_stream(t, f"s{i}", ["v"]) for i in range(n_chains)]
    for i, s in enumerate(srcs):
        node = s
        for d in range(depth):
            node = reg.create_composite(t, f"c{i}_{d}", ["v"], [node],
                                        {"v": f"in0.v + {d + 1}"})
    return cfg, reg, srcs


def _trace(supersteps: int, n_chains: int):
    """Per-superstep post count: quiet (1) -> burst (4 waves across every
    chain) -> quiet (0, drain)."""
    third = supersteps // 3
    plan = []
    for step in range(supersteps):
        if step < third:
            plan.append(1)
        elif step < 2 * third:
            plan.append(4)
        else:
            plan.append(0)
    return plan


def _feed(eng, srcs, waves, ts):
    for w in range(waves):
        for s in srcs:
            eng.post(s, [float(ts + w)], ts)
        ts += 1
    return ts + 1


def _drops(eng):
    c = eng.counters()
    return int(c["dropped_overflow"]), int(c["queued_in"])


def run_static(plan, n_chains, depth, n_shards, K):
    _, reg, srcs = _build(n_chains, depth, n_shards)
    eng = create_engine(reg)
    eng.superstep(K)                          # own closure, pre-measurement
    jax.block_until_ready(eng.state.timestamps)
    ts, dev_s = 1, 0.0
    t_all = time.perf_counter()
    for waves in plan:
        ts = _feed(eng, srcs, waves, ts)
        t0 = time.perf_counter()
        eng.superstep(K)
        jax.block_until_ready(eng.state.timestamps)
        dev_s += n_shards * (time.perf_counter() - t0)
    wall = time.perf_counter() - t_all
    drops, queued = _drops(eng)
    return {"n_shards": n_shards, "device_seconds": dev_s,
            "wall_seconds": wall, "drops": drops, "queued_in": queued,
            "drop_rate": drops / max(queued, 1)}


def run_elastic(plan, n_chains, depth, max_shards, K):
    _, reg, srcs = _build(n_chains, depth, 1)
    eng = create_engine(reg)
    # warm pool: walk the engine itself through every shard count the
    # autoscaler can reach (up and back down) so its per-layout closure
    # cache is fully populated — measured resizes then compile nothing
    counts, n = [], 1
    while n <= max_shards:
        counts.append(n)
        n *= 2
    ts = 1
    for n in counts + counts[-2::-1]:
        eng.resize(n)
        ts = _feed(eng, srcs, 1, ts)
        eng.superstep(K)
    for _ in range(depth):                    # drain warm-pool wavefronts
        eng.superstep(K)
    jax.block_until_ready(eng.state.timestamps)
    drops0, queued0 = _drops(eng)             # counter baseline post-warm
    sc = Autoscaler(eng, min_shards=1, max_shards=max_shards,
                    up=0.15, down=0.03, patience=1, cooldown=1)
    compiles0 = compile_count()
    dev_s, shard_hist, resize_ms = 0.0, [], []
    t_all = time.perf_counter()
    for waves in plan:
        ts = _feed(eng, srcs, waves, ts)
        n = eng.cfg.n_shards
        t0 = time.perf_counter()
        eng.superstep(K)
        jax.block_until_ready(eng.state.timestamps)
        dev_s += n * (time.perf_counter() - t0)
        shard_hist.append(n)
        t0 = time.perf_counter()
        if sc.observe() is not None:          # resize cost charged to
            resize_ms.append(1e3 * (time.perf_counter() - t0))
            dev_s += eng.cfg.n_shards * (time.perf_counter() - t0)
    wall = time.perf_counter() - t_all
    compiles = compile_count() - compiles0
    drops, queued = _drops(eng)
    drops, queued = drops - drops0, queued - queued0
    return {"max_shards": max_shards, "device_seconds": dev_s,
            "wall_seconds": wall, "drops": drops, "queued_in": queued,
            "drop_rate": drops / max(queued, 1),
            "resizes": len(sc.events), "compiles": compiles,
            "shard_history": shard_hist,
            "mean_shards": float(np.mean(shard_hist)),
            "resize_ms": {"mean": float(np.mean(resize_ms)) if resize_ms
                          else 0.0,
                          "max": float(np.max(resize_ms)) if resize_ms
                          else 0.0},
            "scale_events": [{"step": e.step, "from": e.from_shards,
                              "to": e.to_shards, "reason": e.reason,
                              "occupancy": round(e.occupancy, 3)}
                             for e in sc.events]}


def bench(supersteps, n_chains, depth, max_shards, K):
    plan = _trace(supersteps, n_chains)
    # elastic first: its warm pool walk compiles every shape-keyed global
    # jit at every shard count, so the static run starts warm too
    elastic = run_elastic(plan, n_chains, depth, max_shards, K)
    static = run_static(plan, n_chains, depth, max_shards, K)
    return {
        "config": {"supersteps": supersteps, "chains": n_chains,
                   "depth": depth, "max_shards": max_shards, "k": K,
                   "platform": jax.devices()[0].platform},
        "elastic": elastic,
        "static": static,
        "device_seconds_saved_pct":
            100.0 * (1.0 - elastic["device_seconds"]
                     / max(static["device_seconds"], 1e-12)),
        "elastic_wins": bool(
            elastic["device_seconds"] < static["device_seconds"]
            and elastic["drop_rate"] <= static["drop_rate"] + 0.01),
        "retraces_ok": bool(elastic["compiles"] == 0),
    }


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--supersteps", type=int, default=36)
    ap.add_argument("--chains", type=int, default=6)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--max-shards", type=int, default=4)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--json", default=None, help="write results as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: short trace; contracts enforced")
    args = ap.parse_args()
    if args.smoke:
        args.supersteps, args.chains = 18, 4

    res = bench(args.supersteps, args.chains, args.depth, args.max_shards,
                args.k)
    e, s = res["elastic"], res["static"]
    print(f"device-seconds  elastic {e['device_seconds']:8.3f} "
          f"(mean {e['mean_shards']:.2f} shards)   "
          f"static@{s['n_shards']} {s['device_seconds']:8.3f}   "
          f"saved {res['device_seconds_saved_pct']:+.1f}%")
    print(f"drop rate       elastic {e['drop_rate']:.4f} "
          f"({e['drops']}/{e['queued_in']})   "
          f"static {s['drop_rate']:.4f} ({s['drops']}/{s['queued_in']})")
    print(f"resizes {e['resizes']}   compiles during run {e['compiles']}   "
          f"resize mean {e['resize_ms']['mean']:.1f} ms "
          f"max {e['resize_ms']['max']:.1f} ms")
    for ev in e["scale_events"]:
        print(f"  step {ev['step']:3d}  {ev['from']}->{ev['to']} shards  "
              f"({ev['reason']}, occ {ev['occupancy']:.2f})")
    print(f"elastic wins: {res['elastic_wins']}   "
          f"retraces ok: {res['retraces_ok']} (contracts: True / True)")
    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if not res["retraces_ok"]:
        print("WARNING: resizes caused extra recompilation", file=sys.stderr)
        sys.exit(1)
    if not res["elastic_wins"]:
        print("WARNING: elastic lost to static peak provisioning",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
