"""Live subscription churn — the dynamic admission plane vs. static rebuild.

The headline claim of the admission plane (ISSUE 2): a tenant is admitted
on the *running* engine in O(table-edit) with **zero recompilation**.  This
benchmark measures, on a capacity-padded topology:

  * ``admit_ms`` / ``revoke_ms``  — host wall time per live admission /
    revocation (registry mirror + expression compile + jitted table edits);
  * ``rounds_per_s_churn``        — engine rounds/s while every round
    admits one composite and revokes the oldest churned one (steady-state
    subscribe/unsubscribe, the workload of arXiv 1709.01363 §elasticity);
  * ``rounds_per_s_static``       — the same SU load with no churn (upper
    bound: what churn costs);
  * ``rebuild_ms``                — what the *static* alternative pays per
    churn event: re-lowering every table via ``rewire()`` (the pre-PR-2
    answer to topology changes);
  * ``retraces``                  — compiled-step cache growth across the
    churn phase; the admission plane's contract is that this is **0**.

Run ``python -m benchmarks.churn [--nodes N] [--rounds R] [--shards S]
[--json PATH] [--smoke]``.  ``--smoke`` is the CI mode: one measured round,
a tiny topology, exercising every op once (see benchmarks/README.md for
how to read the JSON).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/churn.py`
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


def _build(n_nodes: int, n_shards: int, spare: int):
    """A fan topology: n_nodes/4 sources, the rest composites subscribing
    round-robin, padded with ``spare`` rows of admission headroom."""
    n_sources = max(n_nodes // 4, 1)
    cfg = EngineConfig(
        n_streams=n_nodes, batch=64, queue=max(2048, 8 * n_nodes),
        max_in=4, max_out=16, prog_len=24, n_temps=12,
        n_shards=n_shards,
        exchange_slots=min(64 * 16, 1024) if n_shards > 1 else 0,
    )
    reg = Registry.with_capacity(cfg, max_streams=n_nodes + spare, max_subs=0)
    ten = reg.create_tenant("bench", quota_streams=10 ** 9)
    sources = [reg.create_stream(ten, f"s{i}", ["v"]) for i in range(n_sources)]
    comps = []
    for i in range(n_nodes - n_sources):
        src = sources[i % n_sources]
        comps.append(reg.create_composite(
            ten, f"c{i}", ["v"], [src], transform={"v": f"in0.v + {i % 7}"}))
    return reg, ten, sources, comps


def _post_wave(eng, sources, ts: int):
    for i, s in enumerate(sources):
        eng.post(s, [float(i + ts)], ts=ts)


def bench(n_nodes: int, n_rounds: int, n_shards: int, churn_every: int = 1,
          seed: int = 0):
    spare = max(n_rounds // max(churn_every, 1) + 8, 16)
    reg, ten, sources, comps = _build(n_nodes, n_shards, spare)
    eng = create_engine(reg)

    # ---- warm-up: compile the round and every admission op once ---------
    _post_wave(eng, sources, ts=1)
    eng.round()
    warm = eng.admit_composite(ten, "warm", ["v"], [sources[0]],
                               {"v": "in0.v * 2"})
    eng.swap_program(warm, {"v": "in0.v * 3"})
    eng.admit_subscription(warm, sources[-1])
    eng.revoke_subscription(warm, sources[-1])
    eng.revoke_stream(warm)
    eng.round()
    cache0 = compile_count(eng._step)

    # ---- admit / revoke latency -----------------------------------------
    admit_ms, revoke_ms = [], []
    live = []
    n_lat = min(16, spare - 2)
    for i in range(n_lat):
        t0 = time.perf_counter()
        s = eng.admit_composite(ten, f"lat{i}", ["v"],
                                [sources[i % len(sources)]],
                                {"v": f"in0.v + {i}"})
        jax.block_until_ready(eng.tables.progs)
        admit_ms.append((time.perf_counter() - t0) * 1e3)
        live.append(s)
    for s in live:
        t0 = time.perf_counter()
        eng.revoke_stream(s)
        jax.block_until_ready(eng.tables.active)
        revoke_ms.append((time.perf_counter() - t0) * 1e3)

    # ---- rounds/s under steady churn ------------------------------------
    churned = []
    ts = 2
    t0 = time.perf_counter()
    for r in range(n_rounds):
        if r % churn_every == 0:
            churned.append(eng.admit_composite(
                ten, f"churn{r}", ["v"], [sources[r % len(sources)]],
                {"v": f"in0.v + {r % 11}"}))
            if len(churned) > 4:
                eng.revoke_stream(churned.pop(0))
        _post_wave(eng, sources, ts)
        eng.round()
        ts += 1
    jax.block_until_ready(eng.state.timestamps)
    dt_churn = time.perf_counter() - t0
    retraces = compile_count(eng._step) - cache0

    # ---- rounds/s static baseline (same SU load, no churn) --------------
    t0 = time.perf_counter()
    for r in range(n_rounds):
        _post_wave(eng, sources, ts)
        eng.round()
        ts += 1
    jax.block_until_ready(eng.state.timestamps)
    dt_static = time.perf_counter() - t0

    # ---- the static alternative: full re-lower per churn event ----------
    rebuild_ms = []
    for _ in range(min(4, n_rounds)):
        eng.drain()
        t0 = time.perf_counter()
        eng.rewire()
        jax.block_until_ready(eng.tables.progs)
        rebuild_ms.append((time.perf_counter() - t0) * 1e3)

    c = eng.counters()
    return {
        "config": {"n_nodes": n_nodes, "n_rounds": n_rounds,
                   "n_shards": n_shards, "churn_every": churn_every,
                   "spare_rows": spare,
                   "platform": jax.devices()[0].platform},
        "admit_ms": {"mean": float(np.mean(admit_ms)),
                     "p50": float(np.median(admit_ms)),
                     "max": float(np.max(admit_ms))},
        "revoke_ms": {"mean": float(np.mean(revoke_ms)),
                      "p50": float(np.median(revoke_ms)),
                      "max": float(np.max(revoke_ms))},
        "rebuild_ms": {"mean": float(np.mean(rebuild_ms)),
                       "max": float(np.max(rebuild_ms))},
        "rounds_per_s_churn": n_rounds / dt_churn,
        "rounds_per_s_static": n_rounds / dt_static,
        "retraces": int(retraces),
        "admission_rejected": eng.admission_rejected,
        "counters": {k: int(v) for k, v in c.items()},
    }


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=96)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--churn-every", type=int, default=1)
    ap.add_argument("--json", default=None, help="write results as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: 1 measured round, tiny topology")
    args = ap.parse_args()
    if args.smoke:
        args.nodes, args.rounds = 16, 1

    res = bench(args.nodes, args.rounds, args.shards, args.churn_every)
    print(f"admit   {res['admit_ms']['p50']:8.2f} ms p50 "
          f"({res['admit_ms']['mean']:.2f} mean)")
    print(f"revoke  {res['revoke_ms']['p50']:8.2f} ms p50")
    print(f"rebuild {res['rebuild_ms']['mean']:8.2f} ms mean   "
          "(the static alternative per churn event)")
    print(f"rounds/s  churn {res['rounds_per_s_churn']:8.1f}   "
          f"static {res['rounds_per_s_static']:8.1f}")
    print(f"retraces during churn: {res['retraces']} (contract: 0)")
    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if res["retraces"]:
        print("WARNING: admission caused recompilation", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
