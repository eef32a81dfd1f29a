"""Chaos drill — fault injection, quarantine isolation, supervised recovery.

One deterministic scenario, three runs per shard count, everything seeded
(`repro.launch.chaos`) so any failure replays from its seed:

* **clean** — no injections: the co-tenant throughput baseline and the
  detection-overhead timing arm (breaker armed vs disarmed on the same
  compiled round — the detector is branch-free device math riding the
  round, so the delta must be noise-level);
* **twin** — the poison feed (NaN payloads + a hostile overflow program
  swap on the poison tenant) but *no* process faults: the undisturbed
  reference the recovery must be bit-identical to;
* **chaos** — the same feed under a :class:`repro.launch.supervise.
  Supervisor`, plus a torn newest checkpoint followed by a
  :class:`~repro.launch.chaos.ShardKill`: recovery must skip the torn
  checkpoint (checksum plane), restore the older valid one, replay the
  feed prefix, and land bit-identical to the twin.

Reported per shard count (JSON schema: benchmarks/README.md):

  * ``mttr_s``/``incidents``/``recovered`` — supervisor recovery stats;
  * ``bit_exact``        — chaos-run final snapshot == twin's, leaf for
    leaf (NaN-aware);
  * ``quarantine``       — poison-tenant rows auto-quarantined by the
    device breaker + ``dropped_poisoned``/DLQ accounting;
  * ``cotenant``         — co-tenant emissions in the twin vs the clean
    baseline (isolation: the deficit must be 0);
  * ``overhead``         — armed-vs-disarmed steps/s (detection hot-path
    cost; noise-level by construction);
  * ``retraces``         — compile-cache growth per engine incarnation
    (contract: 0 — quarantine trips, breaker edits and recovery replay
    are all runtime data).

``--smoke`` is the CI mode: tiny geometry, and exits non-zero on any
retrace, failed recovery, or non-identical post-recovery state.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

if __package__ in (None, ""):  # `python benchmarks/chaos.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro.core.config import EngineConfig                    # noqa: E402
from repro.core.engine import StreamEngine                    # noqa: E402
from repro.core.registry import Registry                      # noqa: E402
from repro.launch import chaos as C                           # noqa: E402
from repro.launch.supervise import Supervisor                 # noqa: E402
from repro.launch.compiles import (compile_count,              # noqa: E402
                                   use_compile_cache)

SEED = 11


def _build(n_tenants: int, n_shards: int, checkpoint_every: int):
    """T tenants, each a src stream + one fusable composite subscriber;
    tenant 0 is the (future) poison tenant."""
    cfg = EngineConfig(
        n_streams=max(4 * n_tenants, 16), n_tenants=max(n_tenants, 2),
        channels=1, max_in=4, max_out=4, batch=4 * n_tenants,
        queue=max(64, 8 * n_tenants), prog_len=24, n_consts=8, n_temps=12,
        sink_buffer=4 * n_tenants, retention_slots=2,
        dlq_slots=max(64, 8 * n_tenants), superstep=1,
        checkpoint_every=checkpoint_every, n_shards=n_shards,
        fault_window=8, fault_threshold=2, fault_amp_ceiling=0)
    reg = Registry.with_capacity(cfg)
    flows = []
    for tid in range(n_tenants):
        t = reg.create_tenant(f"t{tid}")
        src = reg.create_stream(t, f"src{tid}", ["v"])
        comp = reg.create_composite(t, f"comp{tid}", ["v"], [src],
                                    {"v": f"src{tid}.v * 2.0 + 1.0"})
        flows.append((t, src, comp))
    if n_shards > 1:
        from repro.distributed.stream_sharding import ShardedStreamEngine
        eng = ShardedStreamEngine(reg)
    else:
        eng = StreamEngine(reg)
    return eng, flows


def _make_feed(sids, n_steps: int, channels: int, poison_steps, seed: int):
    """Precompute the full (step, tenant) -> payload table so the feed is
    a pure function of the step index — the replay-determinism contract
    the supervisor needs.  ``sids`` are the per-tenant source stream ids
    (stable across restore, so the feed survives engine rebuilds); tenant
    0's payload is poisoned on ``poison_steps``."""
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((n_steps, len(sids), channels)) \
        .astype(np.float32)
    for s in poison_steps:
        table[s, 0] = C.poison_payload(rng, channels)
    def feed(eng, step):
        for tid, sid in enumerate(sids):
            eng.post(sid, table[step, tid], ts=10 * step + tid + 1)
    feed.table = table
    return feed


def _snap_equal(a, b) -> bool:
    """Leaf-for-leaf snapshot equality, NaN-aware (poison payloads live
    in the state, so float compares must treat NaN == NaN)."""
    if set(a) != set(b):
        return False
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        eq = np.array_equal(x, y, equal_nan=True) \
            if np.issubdtype(x.dtype, np.floating) else np.array_equal(x, y)
        if not eq:
            return False
    return True


def _tenant_emitted(eng) -> np.ndarray:
    e = np.asarray(eng.state.tenant_emitted)
    return e.sum(axis=0) if e.ndim == 2 else e


def _run_plain(eng, feed, n_steps: int, K: int):
    """Un-supervised drive (clean + twin runs)."""
    t0 = time.perf_counter()
    for step in range(n_steps):
        feed(eng, step)
        eng.superstep(K)
    return time.perf_counter() - t0


def bench_shards(n_shards: int, n_tenants: int, n_steps: int, K: int) -> dict:
    ck_every = max(2, n_steps // 8)
    monkey = C.ChaosMonkey(SEED, n_steps, p_poison=0.3, p_storm=0.0)
    poison_steps = sorted({e.step for e in monkey.events
                           if e.kind == "poison" and e.step < n_steps // 2})
    kill_step = max(2 * ck_every + 1, int(n_steps * 0.6))
    res = {"seed": SEED, "poison_steps": poison_steps,
           "kill_step": kill_step, "checkpoint_every": ck_every}
    retraces = 0

    # ---- clean baseline + detection-overhead timing arm -----------------
    # No poison; the same compiled round with the breaker armed vs
    # disarmed (the knobs are runtime data, so the XLA is identical —
    # the delta is the full hot-path cost of having detection wired in).
    eng, flows = _build(n_tenants, n_shards, 0)
    sids = [f[1].sid for f in flows]
    clean_feed = _make_feed(sids, n_steps, 1, [], SEED)
    scan0 = compile_count(eng._superstep_fn(K))
    eng.superstep(K)                       # warm-up: compile the K-scan
    dt_armed = _run_plain(eng, clean_feed, n_steps, K)
    clean_emitted = _tenant_emitted(eng)
    retraces += compile_count(eng._superstep_fns[K]) - scan0 - 1
    eng2, _ = _build(n_tenants, n_shards, 0)
    eng2.set_breaker(threshold=0, amp_ceiling=0)      # disarmed, same XLA
    scan0 = compile_count(eng2._superstep_fn(K))
    eng2.superstep(K)
    dt_off = _run_plain(eng2, clean_feed, n_steps, K)
    retraces += compile_count(eng2._superstep_fns[K]) - scan0 - 1
    res["overhead"] = {
        "armed_steps_per_s": n_steps / dt_armed,
        "disarmed_steps_per_s": n_steps / dt_off,
        "overhead_frac": max(0.0, 1.0 - dt_off / dt_armed),
    }

    # ---- undisturbed twin: poison feed, no process faults ---------------
    # No warm-up superstep: the supervised run's step index must equal the
    # engine's _steps_done for prefix replay, and the twin must match it
    # round-for-round for the bit-exactness check.
    feed = _make_feed(sids, n_steps, 1, poison_steps, SEED)
    twin, _ = _build(n_tenants, n_shards, 0)
    scan0 = compile_count(twin._superstep_fn(K))
    _run_plain(twin, feed, n_steps, K)
    retraces += compile_count(twin._superstep_fns[K]) - scan0 - 1
    twin_arrays, _ = twin.snapshot()
    twin_emitted = _tenant_emitted(twin)
    fc = twin.fault_counters()

    # ---- supervised chaos run: tear newest checkpoint, then kill --------
    ckdir = tempfile.mkdtemp(prefix="chaos_ck_")
    try:
        eng3, _ = _build(n_tenants, n_shards, ck_every)
        tear_rng = np.random.default_rng(SEED + 2)

        def chaos_hook(e, step):
            if step == kill_step:
                e._ckpt.wait()             # the torn victim must be on disk
                C.corrupt_checkpoint(ckdir, tear_rng, mode="truncate")
                raise C.ShardKill(f"injected shard kill at step {step}")

        sup = Supervisor(eng3, ckdir, feed=feed, chaos=chaos_hook, K=K,
                         escalate_after=10**9)   # observational blame only
        scan0 = compile_count(eng3._superstep_fn(K))
        report = sup.run(n_steps)
        final = sup.engine
        # the first engine and each restored one compile the scan once
        retraces += compile_count(final._superstep_fns[K]) - scan0 \
            - (1 + len(report.incidents))
        if final._ckpt is not None:
            final._ckpt.wait()
        chaos_arrays, _ = final.snapshot()
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)

    bit_exact = _snap_equal(twin_arrays, chaos_arrays)
    cot_clean = float(clean_emitted[1:].sum())
    cot_twin = float(twin_emitted[1:].sum())
    res.update({
        "recovered": report.recovered,
        "mttr_s": report.mttr_s,
        "incidents": [{"step": i.step, "kind": i.kind,
                       "restored_step": i.restored_step,
                       "retries": i.retries,
                       "replayed_steps": i.replayed_steps,
                       "downtime_s": i.downtime_s,
                       "blamed": i.blamed} for i in report.incidents],
        "bit_exact": bit_exact,
        "quarantine": {
            "quarantined_sids":
                [int(s) for s in np.nonzero(fc["quarantined"])[0]],
            "fault_total": int(fc["fault_total"].sum()),
            "dropped_poisoned": twin.counters()["dropped_poisoned"],
            "nonfinite": twin.counters()["nonfinite"],
        },
        "cotenant": {
            "clean_emitted": cot_clean,
            "faulted_emitted": cot_twin,
            "deficit_frac": 0.0 if cot_clean == 0
                else max(0.0, 1.0 - cot_twin / cot_clean),
        },
        "retraces": int(retraces),
    })
    return res


def bench(n_tenants: int, n_steps: int, K: int, shard_counts) -> dict:
    res = {
        "config": {"tenants": n_tenants, "steps": n_steps, "k": K,
                   "seed": SEED, "platform": jax.devices()[0].platform},
        "shards": {},
    }
    for n in shard_counts:
        res["shards"][str(n)] = bench_shards(n, n_tenants, n_steps, K)
    sh = res["shards"].values()
    res["retraces"] = sum(s["retraces"] for s in sh)
    res["recovered"] = all(s["recovered"] for s in sh)
    res["bit_exact"] = all(s["bit_exact"] for s in sh)
    return res


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--shards", default="1,2",
                    help="comma-separated shard counts to sweep")
    ap.add_argument("--json", default=None, help="write results as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny geometry, hard contract gates")
    args = ap.parse_args()
    if args.smoke:
        args.tenants, args.steps, args.k = 3, 12, 2
        if args.shards == "1,2":
            args.shards = "1"
    shard_counts = [int(s) for s in args.shards.split(",") if s]

    res = bench(args.tenants, args.steps, args.k, shard_counts)
    for n, r in res["shards"].items():
        q = r["quarantine"]
        print(f"shards={n}: recovered={r['recovered']} "
              f"bit_exact={r['bit_exact']} mttr={r['mttr_s'] * 1e3:.1f}ms "
              f"retraces={r['retraces']}")
        print(f"  quarantined={q['quarantined_sids']} "
              f"faults={q['fault_total']} "
              f"dropped_poisoned={q['dropped_poisoned']} "
              f"nonfinite={q['nonfinite']}")
        print(f"  cotenant deficit {r['cotenant']['deficit_frac']:.4f}   "
              f"detection overhead {r['overhead']['overhead_frac']:.4f}")
    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if res["retraces"]:
        print("WARNING: chaos drill caused recompilation", file=sys.stderr)
        sys.exit(1)
    if not res["recovered"]:
        print("WARNING: supervisor failed to recover", file=sys.stderr)
        sys.exit(1)
    if not res["bit_exact"]:
        print("WARNING: post-recovery state differs from undisturbed twin",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
