"""Superstep execution plane — rounds/s vs. fused rounds per dispatch.

The per-round API (`StreamEngine.round()`) pays one device->host->device
trip per round: ship an ingest batch, run one jitted step, read the sink
back.  The superstep plane (`make_superstep`) fuses K rounds into one
compiled ``lax.scan`` fed by the on-device ingest ring and draining into
the on-device sink spool, so the same K rounds cost one staged transfer,
one dispatch and one readback.  Sustained throughput under backlog is the
primary stream-processing metric (Shukla & Simmhan, IoT benchmarks); this
sweep records rounds/s for K ∈ {1, 8, 64} at 1 and 4 shards — the repo's
first recorded perf baseline — and asserts the plane's retrace contract.

Run ``python -m benchmarks.superstep [--nodes N] [--supersteps R]
[--ks 1,8,64] [--shards 1,4] [--json BENCH_superstep.json] [--smoke]``.
``--smoke`` is the CI mode: a tiny topology and few supersteps, still
failing (exit 1) if any superstep retraces.  The JSON schema is described
in benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/superstep.py`
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


def _build(n_nodes: int, n_shards: int):
    """Fan topology: n_nodes/4 sources, the rest composites subscribing
    round-robin — every round has ingest, fan-out and emission work.

    The sizing is deliberately *latency-bound*: small batch/fan-out/queue
    keep one round's XLA compute well under the per-dispatch host cost, so
    the sweep isolates what the superstep plane actually removes — the
    device->host->device boundary per round.  (Compute-bound rounds — big
    batches, deep programs — amortize the boundary by themselves; see
    benchmarks/sharded_scaling.py for that regime.)"""
    n_sources = max(n_nodes // 4, 1)
    cfg = EngineConfig(
        n_streams=n_nodes, batch=8, queue=max(48, 2 * n_nodes),
        max_in=4, max_out=4, prog_len=16, n_temps=12,
        sink_buffer=32,            # >= per-round emissions; keeps the
        n_shards=n_shards,         # K*sink spool proportionate
        exchange_slots=8 * 4 if n_shards > 1 else 0,
    )
    reg = Registry(cfg)
    ten = reg.create_tenant("bench", quota_streams=10 ** 9)
    sources = [reg.create_stream(ten, f"s{i}", ["v"]) for i in range(n_sources)]
    n_comp = min(n_nodes - n_sources, n_sources * cfg.max_out)
    for i in range(n_comp):
        reg.create_composite(ten, f"c{i}", ["v"], [sources[i % n_sources]],
                             transform={"v": f"in0.v + {i % 7}"})
    return reg, sources


def _post_burst(eng, sources, K: int, ts: int) -> int:
    """K waves of one SU per source: the staging packs exactly one wave
    into each of the superstep's K rounds."""
    for k in range(K):
        for i, s in enumerate(sources):
            eng.post(s, [float(i + ts + k)], ts=ts + k)
    return ts + K


def bench_one(n_nodes: int, K: int, n_shards: int, n_supersteps: int):
    reg, sources = _build(n_nodes, n_shards)
    eng = create_engine(reg)

    # warm-up: compile the scan (and the staging op) once
    ts = _post_burst(eng, sources, K, ts=1)
    eng.superstep(K)
    jax.block_until_ready(eng.state.timestamps)
    cache0 = compile_count(eng._superstep_fns[K])

    t0 = time.perf_counter()
    for _ in range(n_supersteps):
        ts = _post_burst(eng, sources, K, ts)
        eng.superstep(K)
    jax.block_until_ready(eng.state.timestamps)
    dt = time.perf_counter() - t0

    c = eng.counters()
    retraces = compile_count(eng._superstep_fns[K]) - cache0
    return {
        "K": K, "shards": n_shards, "path": eng._path,
        "rounds_per_s": n_supersteps * K / dt,
        "supersteps_per_s": n_supersteps / dt,
        "retraces": int(retraces),
        "counters": {k: int(v) for k, v in c.items()},
    }


def bench_round_api(n_nodes: int, n_shards: int, n_rounds: int):
    """The pre-superstep baseline: one host iteration per round."""
    reg, sources = _build(n_nodes, n_shards)
    eng = create_engine(reg)
    ts = _post_burst(eng, sources, 1, ts=1)
    eng.round()
    jax.block_until_ready(eng.state.timestamps)
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        ts = _post_burst(eng, sources, 1, ts)
        eng.round()
    jax.block_until_ready(eng.state.timestamps)
    return n_rounds / (time.perf_counter() - t0)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--supersteps", type=int, default=20,
                    help="measured supersteps per (K, shards) point")
    ap.add_argument("--ks", default="1,8,64")
    ap.add_argument("--shards", default="1,4")
    ap.add_argument("--json", default="BENCH_superstep.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny topology, few supersteps")
    args = ap.parse_args()
    ks = [int(x) for x in args.ks.split(",")]
    shard_counts = [int(x) for x in args.shards.split(",")]
    if args.smoke:
        args.nodes, args.supersteps = 16, 3
        ks = sorted(set(ks) & {1, 8}) or [1, 8]
        shard_counts = [s for s in shard_counts if s == 1] or [1]

    n_dev = len(jax.devices())
    res = {"config": {"nodes": args.nodes, "supersteps": args.supersteps,
                      "platform": jax.devices()[0].platform,
                      "devices": n_dev, "smoke": bool(args.smoke)},
           "sweep": [], "round_api": {}}
    print(f"{'shards':>7} {'K':>4} {'rounds/s':>10} {'retraces':>9}")
    for s in shard_counts:
        if s > n_dev:
            print(f"{s:>7}      (skipped: only {n_dev} devices)")
            continue
        rps = bench_round_api(args.nodes, s, max(args.supersteps, 5))
        res["round_api"][str(s)] = rps
        print(f"{s:>7} {'api':>4} {rps:>10.1f} {'-':>9}")
        for K in ks:
            r = bench_one(args.nodes, K, s, args.supersteps)
            res["sweep"].append(r)
            print(f"{s:>7} {K:>4} {r['rounds_per_s']:>10.1f} "
                  f"{r['retraces']:>9}")

    by = {(r["shards"], r["K"]): r["rounds_per_s"] for r in res["sweep"]}
    lo, hi = min(ks), max(ks)
    if (1, lo) in by and (1, hi) in by and lo != hi:
        res["speedup_1shard"] = {f"K{hi}_vs_K{lo}": by[(1, hi)] / by[(1, lo)]}
        print(f"1-shard speedup K={hi} vs K={lo}: "
              f"{by[(1, hi)] / by[(1, lo)]:.2f}x")

    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if any(r["retraces"] for r in res["sweep"]):
        print("WARNING: a superstep caused recompilation", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
