"""IoT application workloads — per-tenant end-to-end latency under SLOs.

The paper's bottom line is what tenants *feel* on shared infrastructure:
this benchmark replays one deterministic sensor trace (diurnal ramps +
per-device bursts, ``repro.workloads.traces``) through the three
RIoTBench-style dataflow shapes — ETL (parse→filter→interpolate→
annotate), STATS (smoothing + ``window_agg`` windows) and PRED (feature
→ model-backed stream → serving bridge → decision) — side by side on one
engine, at 1 and 4 shards, and reports per-tenant ingest→sink latency
percentiles off the device-resident ingest-stamp plane:

  * ``tenants``/``kinds``/``total`` — p50/p95/p99 latency (in engine
    rounds), SLO violation counts and rates from the
    :class:`repro.core.slo.SLOTracker` histograms;
  * ``steps_per_s``   — trace steps (one K-round superstep each, plus
    bridge pump/drain) per second;
  * ``retraces``      — superstep-path compile-cache growth over the
    whole replay.  Latency is read back from arrays the sink already
    carries, so the contract, as everywhere in this repo, is **0** (the
    benchmark exits non-zero);
  * empty latency records also exit non-zero — a latency plane that
    observes nothing is a broken latency plane, not a fast one.

Run ``python -m benchmarks.iot [--tenants N] [--steps R] [--k K]
[--shards 1,4] [--json PATH] [--smoke]``.  ``--smoke`` is the CI mode
(few tenants/steps; latency numbers are not meaningful but the retrace
and non-empty contracts are enforced).  JSON schema: benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/iot.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro import configs                                     # noqa: E402
from repro.models import model as M                           # noqa: E402
from repro.serving import ContinuousBatcher                   # noqa: E402
from repro.workloads import TraceConfig, build_suite, drive   # noqa: E402
from repro.workloads.runner import wire_pred                  # noqa: E402
from repro.launch.compiles import (compile_count,              # noqa: E402
                                   use_compile_cache)

KINDS = ("etl", "stats", "pred")
SLO_ROUNDS = 16        # every tenant's latency target, in engine rounds


def _make_batcher(slots: int = 4):
    """A real (tiny) decode server so PRED latency includes serving."""
    cfg = dataclasses.replace(configs.get_smoke("gemma3-1b"), vocab=128)
    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    return ContinuousBatcher(cfg, params, slots=slots, max_len=48)


def _kind_stats(slo, flows, kind):
    """Aggregate one kind's tenants into p50/p95/p99 by summing their
    latency histograms (same nearest-rank semantics as the tracker)."""
    tids = [f.tenant.tid for f in flows if f.kind == kind]
    h = slo.hist[tids].sum(axis=0)
    total = int(h.sum())
    viol = int(slo.violations[tids].sum())
    if total == 0:
        return {"count": 0, "p50": -1, "p95": -1, "p99": -1,
                "violations": 0, "violation_rate": 0.0}
    cum = np.cumsum(h)

    def pct(q):
        rank = max(1, int(np.ceil(q / 100.0 * total)))
        return (int(np.searchsorted(cum, rank)) + 1) * slo.bucket_width - 1

    return {"count": total, "p50": pct(50), "p95": pct(95), "p99": pct(99),
            "violations": viol, "violation_rate": viol / total}


def bench_shards(n_shards: int, tenants: int, steps: int, K: int,
                 seed: int) -> dict:
    """One full trace replay at ``n_shards``; returns the latency report
    plus the retrace count for this engine's superstep path."""
    suite = build_suite(
        tenants, kinds=KINDS, n_shards=n_shards, slo_rounds=SLO_ROUNDS,
        trace=TraceConfig(n_devices=tenants, rounds=steps, seed=seed))
    wire_pred(suite, _make_batcher())
    eng = suite.engine
    eng.superstep(K)                       # warm-up: trace the K-scan once
    cache0 = compile_count(eng._superstep_fns[K])
    t0 = time.perf_counter()
    out = drive(suite, K=K)
    dt = time.perf_counter() - t0
    retraces = compile_count(eng._superstep_fns[K]) - cache0
    rep = out["slo_report"]
    return {
        "records": out["records"],
        "steps_per_s": steps / dt,
        "retraces": retraces,
        "kinds": {k: _kind_stats(suite.slo, suite.flows, k) for k in KINDS},
        "tenants": {str(tid): dict(
            r, kind=next(f.kind for f in suite.flows
                         if f.tenant.tid == tid))
            for tid, r in rep["tenants"].items()},
        "total": rep["total"],
    }


def bench(tenants: int, steps: int, K: int, shard_counts) -> dict:
    res = {
        "config": {"tenants": tenants, "steps": steps, "k": K,
                   "kinds": list(KINDS), "slo_rounds": SLO_ROUNDS,
                   "seed": 7, "platform": jax.devices()[0].platform},
        "shards": {},
    }
    for n in shard_counts:
        res["shards"][str(n)] = bench_shards(n, tenants, steps, K, seed=7)
    res["retraces"] = sum(s["retraces"] for s in res["shards"].values())
    res["records"] = sum(s["records"] for s in res["shards"].values())
    return res


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=24)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--shards", default="1,4",
                    help="comma-separated shard counts to sweep")
    ap.add_argument("--json", default=None, help="write results as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: few tenants/steps")
    args = ap.parse_args()
    if args.smoke:
        args.tenants, args.steps = 6, 10
    shard_counts = [int(s) for s in args.shards.split(",") if s]

    res = bench(args.tenants, args.steps, args.k, shard_counts)
    for n, r in res["shards"].items():
        t = r["total"]
        print(f"shards={n}: {r['records']} records   "
              f"p50/p95/p99 {t['p50']}/{t['p95']}/{t['p99']} rounds   "
              f"violation_rate {t['violation_rate']:.3f}   "
              f"{r['steps_per_s']:.1f} steps/s   retraces {r['retraces']}")
        for k, ks in r["kinds"].items():
            print(f"  {k:<6} n={ks['count']:<5} p50/p95/p99 "
                  f"{ks['p50']}/{ks['p95']}/{ks['p99']}   "
                  f"violation_rate {ks['violation_rate']:.3f}")
    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if res["retraces"]:
        print("WARNING: trace replay caused recompilation", file=sys.stderr)
        sys.exit(1)
    if res["records"] == 0 or any(
            s["records"] == 0 for s in res["shards"].values()):
        print("WARNING: latency plane observed no records", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
