"""Scheduler hot path — rounds/s vs queue depth, lexsort vs packed vs
fused round.

The pop is the engine's per-round serial bottleneck: the lexsort
scheduler pays two full-queue multi-key sorts plus a (Q, T) rank cumsum
— O(Q log Q) over *all* ``queue`` slots — to extract ``batch`` << Q
winners, once per round and K times inside every superstep scan.  The
packed scheduler (`EngineConfig.scheduler="packed"`, the default)
replaces that with a selection pop (`repro.kernels.sched_pop`):
O(Q·batch) vectorized argmin steps, no sort.  Pop cost therefore scales
*linearly* in ``queue`` — this sweep records rounds/s for queue_slots ∈
{256, 1024, 4096} on a deliberately latency-bound topology (small batch,
shallow programs: the round is dominated by the scheduler, not the VM),
with the queue kept saturated so the sort actually has a full queue to
chew on.  The third variant, ``fused`` (`EngineConfig.fused_round`, the
default), layers the fused round on the packed pop: stages 1-3 as one
operation plus the O(Q) free-slot search on both enqueue edges — the
other per-round cost that scales with ``queue``.

Run ``python -m benchmarks.scheduler [--rounds R] [--queues 256,1024,4096]
[--json BENCH_sched.json] [--min-speedup X] [--min-fused-speedup X]
[--no-fused] [--smoke]``.  ``--smoke`` is the CI mode: one tiny queue,
few rounds, still failing (exit 1) if any round retraces — and, like the
full run, if the fused variant loses to the staged packed one.  All
variants are timed in *interleaved* blocks so host drift cancels.  JSON
schema: benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/scheduler.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro.core import EngineConfig, Registry, create_engine  # noqa: E402
from repro.launch.compiles import (compile_count,              # noqa: E402
                                   use_compile_cache)

N_SOURCES = 8           # posted every round (ingest is capped at batch)
FAN = 8                 # L1 composites per source: the amplification
BATCH = 8               # small on purpose: B << Q isolates the pop


# variant -> (EngineConfig.scheduler, EngineConfig.fused_round)
VARIANTS = {"lexsort": ("lexsort", False),
            "packed": ("packed", False),
            "fused": ("packed", True)}


def _build(queue_slots: int, variant: str):
    """Two-hop fan topology sized to pin the queue at capacity: each of
    the 8 sources (2 per tenant, tenants weighted 4:3:2:1) feeds FAN L1
    composites, each of which feeds one terminal L2 — so every popped
    source SU *re-enqueues* FAN L1 SUs (stage-4 fan-out amplification,
    the part the per-round ingest cap cannot throttle).  Posting all
    sources every round injects 8 SUs whose amplified backlog grows the
    queue by ~FAN·BATCH per round until it saturates, and keeps it
    pinned there through the measured window — identical load under
    both schedulers."""
    n_nodes = N_SOURCES * (2 + FAN)
    scheduler, fused = VARIANTS[variant]
    cfg = EngineConfig(
        n_streams=n_nodes, n_tenants=4, batch=BATCH, queue=queue_slots,
        max_in=max(FAN, 2), max_out=FAN, prog_len=16, n_temps=12,
        sink_buffer=BATCH * FAN, scheduler=scheduler, fused_round=fused,
    )
    reg = Registry(cfg)
    tenants = [reg.create_tenant(f"t{i}", quota_streams=10 ** 9)
               for i in range(4)]
    srcs = []
    for i in range(N_SOURCES):
        ten = tenants[i % 4]
        s = reg.create_stream(ten, f"s{i}", ["v"])
        srcs.append(s)
        l1 = [reg.create_composite(ten, f"c{i}_{j}", ["v"], [s],
                                   {"v": f"in0.v + {j}"})
              for j in range(FAN)]
        reg.create_composite(ten, f"z{i}", ["v"], l1, {"v": "in0.v * 2"})
    eng = create_engine(reg)
    for i, t in enumerate(tenants):
        eng.set_weight(t, 4 - i)
    return eng, srcs


class _Phase:
    """One engine (one scheduler) under the saturating load, with its
    warm-up, accumulated timed rounds and retrace baseline."""

    def __init__(self, queue_slots: int, variant: str):
        self.eng, self.srcs = _build(queue_slots, variant)
        assert self.eng._path == ("fused" if VARIANTS[variant][1]
                                  else "staged")
        self.ts = 1
        self.time = 0.0
        self.rounds = 0
        self._wave()
        self.eng.round()                       # trace once
        # saturate: amplification grows the queue by ~FAN*BATCH per round
        fill = queue_slots // (FAN * BATCH) + 16
        for _ in range(fill):
            self._wave()
            self.eng.round()
        jax.block_until_ready(self.eng.state.timestamps)
        self.cache0 = compile_count(self.eng._step)

    def _wave(self):
        for i, s in enumerate(self.srcs):
            self.eng.post(s, [float(i + self.ts)], self.ts)
        self.ts += 1

    def occupancy(self) -> int:
        return int(np.asarray(self.eng.state.q_valid).sum())

    def run_block(self, n: int) -> None:
        t0 = time.perf_counter()
        for _ in range(n):
            self._wave()
            self.eng.round()
        jax.block_until_ready(self.eng.state.timestamps)
        self.time += time.perf_counter() - t0
        self.rounds += n

    def report(self, queue_slots: int, variant: str) -> dict:
        return {
            "queue_slots": queue_slots,
            "scheduler": variant,
            "rounds_per_s": self.rounds / self.time,
            "queue_occupancy": self.occupancy(),
            "retraces": compile_count(self.eng._step) - self.cache0,
            "counters": {k: int(v) for k, v in self.eng.counters().items()},
        }


def bench_queue(queue_slots: int, rounds: int, variants):
    """All variants at one queue depth, timed in interleaved blocks
    (same wall-clock neighborhood -> host drift cancels)."""
    phases = {v: _Phase(queue_slots, v) for v in variants}
    block = max(rounds // 4, 1)
    done = 0
    while done < rounds:
        n = min(block, rounds - done)
        for p in phases.values():
            p.run_block(n)
        done += n
    return [p.report(queue_slots, name) for name, p in phases.items()]


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60,
                    help="measured rounds per (queue, scheduler) point")
    ap.add_argument("--queues", default="256,1024,4096")
    ap.add_argument("--json", default="BENCH_sched.json")
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="exit non-zero if packed/lexsort rounds/s at the "
                         "largest queue falls below this (0 = record only)")
    ap.add_argument("--min-fused-speedup", type=float, default=1.0,
                    help="exit non-zero if fused/packed rounds/s at the "
                         "largest queue falls below this (default: the "
                         "fused round must at least not lose)")
    ap.add_argument("--no-fused", action="store_true",
                    help="drop the fused-round variant from the sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: one small queue, few rounds")
    args = ap.parse_args()
    queues = [int(x) for x in args.queues.split(",")]
    if args.smoke:
        # enough measured rounds that the fused-vs-staged gate below is
        # judging throughput, not scheduler-jitter noise, while the whole
        # smoke stays a few seconds
        queues, args.rounds = [256], 24
    variants = [v for v in VARIANTS if v != "fused" or not args.no_fused]

    res = {"config": {"rounds": args.rounds, "sources": N_SOURCES,
                      "fan": FAN, "batch": BATCH,
                      "platform": jax.devices()[0].platform,
                      "smoke": bool(args.smoke)},
           "sweep": [], "speedup": {}, "fused_speedup": {}}
    print(f"{'queue':>6} {'scheduler':>9} {'rounds/s':>10} {'occ':>6} "
          f"{'retraces':>9}")
    for q in queues:
        rows = bench_queue(q, args.rounds, variants)
        res["sweep"] += rows
        by = {r["scheduler"]: r for r in rows}
        res["speedup"][str(q)] = (by["packed"]["rounds_per_s"]
                                  / by["lexsort"]["rounds_per_s"])
        if "fused" in by:
            res["fused_speedup"][str(q)] = (by["fused"]["rounds_per_s"]
                                            / by["packed"]["rounds_per_s"])
        for r in rows:
            print(f"{q:>6} {r['scheduler']:>9} {r['rounds_per_s']:>10.1f} "
                  f"{r['queue_occupancy']:>6} {r['retraces']:>9}")
        print(f"{q:>6} {'speedup':>9} {res['speedup'][str(q)]:>9.2f}x")
        if "fused" in by:
            print(f"{q:>6} {'fused':>9} "
                  f"{res['fused_speedup'][str(q)]:>9.2f}x")

    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if any(r["retraces"] for r in res["sweep"]):
        print("WARNING: a scheduler round caused recompilation",
              file=sys.stderr)
        sys.exit(1)
    top = str(max(queues))
    if args.min_speedup and res["speedup"][top] < args.min_speedup:
        print(f"WARNING: packed speedup {res['speedup'][top]:.2f}x at "
              f"queue={top} below required {args.min_speedup}x",
              file=sys.stderr)
        sys.exit(1)
    if res["fused_speedup"] \
            and res["fused_speedup"][top] < args.min_fused_speedup:
        print(f"WARNING: fused speedup {res['fused_speedup'][top]:.2f}x at "
              f"queue={top} below required {args.min_fused_speedup}x",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
