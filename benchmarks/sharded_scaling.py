"""Sharded stream engine scaling sweep — rounds/sec per shard count.

Runs everywhere: forces host-platform devices on CPU (set before the first
jax import), so ``python -m benchmarks.sharded_scaling`` works on a laptop
and on a real multi-device backend alike.  On forced host devices the
collectives share one physical CPU, so the sweep demonstrates correctness
and per-round cost, not speedup — scale-out wins need a real device mesh
where each shard has its own compute.

    python -m benchmarks.sharded_scaling [--shards 1,2,4,8] [--nodes 96]
                                         [--rounds 50] [--quick]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/sharded_scaling.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import dataclasses                                            # noqa: E402

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro.core import EngineConfig, create_engine            # noqa: E402
from repro.launch.compiles import use_compile_cache           # noqa: E402
from benchmarks.topologies import TopoSpec, build_registry, generate  # noqa: E402


def bench_one(n_shards: int, n_nodes: int, n_rounds: int, seed: int = 0):
    spec = TopoSpec(f"scale-{n_nodes}", n_nodes, max(n_nodes // 3, 2),
                    mean_in=3.0, max_in=8, seed=seed)
    inputs = generate(spec)
    max_in = max((len(i) for i in inputs), default=1)
    out_deg = np.zeros(n_nodes, int)
    for ins in inputs:
        for u in ins:
            out_deg[u] += 1
    cfg = EngineConfig(
        n_streams=n_nodes, batch=64, queue=max(2048, 8 * n_nodes),
        max_in=max(max_in, 1), max_out=max(int(out_deg.max(initial=1)), 1),
        prog_len=max(16, 3 * max_in + 4), n_temps=max(16, max_in + 4),
        n_shards=n_shards,
        # keep the exchange affordable in the sweep; drops are counted
        exchange_slots=min(64 * max(int(out_deg.max(initial=1)), 1), 512),
    )
    reg, nodes, cfg = build_registry(inputs, cfg)
    eng = create_engine(reg)
    sources = [n for n in nodes if not n.composite]

    # warm up / compile one round
    for i, s in enumerate(sources):
        eng.post(s, [float(i)], ts=1)
    eng.round()

    t0 = time.perf_counter()
    ts = 2
    for r in range(n_rounds):
        for i, s in enumerate(sources):
            eng.post(s, [float(i + r)], ts=ts)
        eng.round()
        ts += 1
    # block on the final state
    _ = np.asarray(eng.state.timestamps)
    dt = time.perf_counter() - t0
    c = eng.counters()
    return n_rounds / dt, c


def main(shard_counts=(1, 2, 4, 8), n_nodes=96, n_rounds=50):
    use_compile_cache()
    n_dev = len(jax.devices())
    print(f"devices: {n_dev} ({jax.devices()[0].platform})")
    print(f"{'shards':>7} {'rounds/s':>10} {'emitted':>9} {'dropped':>8}")
    for s in shard_counts:
        if s > n_dev:
            print(f"{s:>7}    (skipped: only {n_dev} devices)")
            continue
        rps, c = bench_one(s, n_nodes, n_rounds)
        print(f"{s:>7} {rps:>10.1f} {c['emitted']:>9} "
              f"{c['dropped_overflow']:>8}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", default="1,2,4,8")
    ap.add_argument("--nodes", type=int, default=96)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    counts = tuple(int(x) for x in args.shards.split(","))
    if args.quick:
        main(counts, n_nodes=48, n_rounds=10)
    else:
        main(counts, n_nodes=args.nodes, n_rounds=args.rounds)
