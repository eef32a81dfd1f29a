"""End-to-end bring-up check of the IoT pipeline suite on TPU.

One process drives the engine's main path through its user entry points
(``repro.workloads.build_suite`` + ``drive``) at a deployment size:
1,024 tenants running ETL and STATS pipelines in alternation (3,586
stream rows), ``batch=256``, ``queue=8192``, K=4 supersteps, replaying a
two-day diurnal sensor trace (48 rounds, six STATS windows of 8 rounds,
bursts on).  PRED flows are left out: their model has to come from a
configuration at published widths, which this check does not load.

One chip (the default): the suite runs on the fused round, then again on
the pure-XLA staged round with the ``lexsort`` scheduler (no Pallas
kernel); sink records, final state and STATS aggregates must agree bit
for bit.  ``--chips 4``: the same suite at ``n_shards=4`` across four
chips against a 1-device engine in the same process, padded to the same
row count; nothing else runs.

Every line before the last is a set-up fact, not a benchmark.  The last
line is ``{"ok": true, "device": {...}}``.  Any failed check raises and
exits non-zero; with no TPU the script exits non-zero before any work.

    python chip_smoke.py [--seed N] [--chips {1,4}]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro.launch.compiles import compile_count, use_compile_cache  # noqa
from repro.workloads import (SensorTrace, TraceConfig, build_suite,  # noqa
                             drive)

N_TENANTS = 1024
KINDS = ("etl", "stats")
BATCH, QUEUE, K = 256, 8192, 4
ROUNDS = 48            # two simulated days of the default 24-round period
WINDOW = 8             # STATS window (rounds), six per trace


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def run_suite(seed: int, *, fused: bool, scheduler: str = "packed",
              n_shards: int = 1, n_streams: int = 0) -> dict:
    """Build one suite, warm every program the replay uses, replay the
    trace once and return its results plus the set-up facts."""
    tcfg = TraceConfig(n_devices=N_TENANTS, rounds=ROUNDS, seed=seed)
    over = {"superstep": K, "scheduler": scheduler}
    if n_streams:
        over["n_streams"] = n_streams
    suite = build_suite(N_TENANTS, kinds=KINDS, n_shards=n_shards,
                        trace=tcfg, window=WINDOW, batch=BATCH, queue=QUEUE,
                        fused_round=fused, cfg_overrides=over)
    eng = suite.engine

    # warm-up: an empty superstep compiles the scan and the ring edit, an
    # empty sink the window push, one read the window aggregate kernel
    t0 = time.perf_counter()
    empty = eng.spool_sinks(eng.superstep(K))[0]
    suite.stats.push_sink(empty)
    jax.block_until_ready(suite.stats.aggregates())
    jax.block_until_ready(eng.state)
    compile_s = time.perf_counter() - t0
    # the warm-up's program, lowered again for its HLO text (the
    # persistent compile cache serves the second compile)
    args = (eng.tables, eng.gmap) if n_shards > 1 else (eng.tables,)
    hlo = eng._superstep_fns[K].lower(
        *args, eng.state, eng._ring).compile().as_text()

    spools = []
    run_superstep = eng.superstep

    def superstep(k=None):
        spool = run_superstep(k)
        spools.append((eng._last_base, jax.device_get(spool)))
        return spool

    eng.superstep = superstep
    c0 = compile_count()
    t0 = time.perf_counter()
    res = drive(suite, K=K)
    jax.block_until_ready(eng.state)
    run_s = time.perf_counter() - t0
    n_compiles = compile_count() - c0

    recs = []
    for base, spool in spools:
        for k, sink in enumerate(eng.spool_sinks(spool)):
            valid = np.asarray(sink.valid).reshape(-1)
            n = int(valid.sum())
            vals = np.asarray(sink.vals).reshape(valid.shape[0], -1)[valid]
            recs.append(np.concatenate([
                np.stack([np.asarray(sink.sid).reshape(-1)[valid],
                          np.asarray(sink.ts).reshape(-1)[valid],
                          np.asarray(sink.its).reshape(-1)[valid],
                          np.full((n,), base + k, np.int32)], axis=1),
                vals.view(np.int32)], axis=1).astype(np.int32))
    if n_shards > 1:
        plan = eng.plan
        values = np.asarray(eng.state.values).reshape(
            plan.n_shards * plan.n_local, -1)[plan.sid_to_flat]
        stamps = np.asarray(eng.state.timestamps).reshape(-1)[
            plan.sid_to_flat]
    else:
        values = np.asarray(eng.state.values)
        stamps = np.asarray(eng.state.timestamps)
    posted = sum(len(dev) for _, dev, _ in SensorTrace(tcfg).steps())
    return {
        "cfg": suite.cfg, "path": eng._path, "n_shards": n_shards,
        "custom_call": "tpu_custom_call" in hlo,
        "compile_s": compile_s, "run_s": run_s, "compiles": n_compiles,
        "posted": posted, "counters": eng.counters(),
        "records": np.concatenate(recs) if recs
        else np.zeros((0, 4 + suite.cfg.channels), np.int32),
        "values": values.view(np.int32), "timestamps": stamps,
        "aggregates": {k: np.asarray(v).view(np.int32)
                       for k, v in res["aggregates"].items()},
        "latency_records": res["records"],
    }


def report(tag: str, r: dict, expect_path: str, expect_kernel: bool) -> None:
    c = r["counters"]
    agg_rows = int((r["aggregates"]["count"] != 0).any(axis=1).sum())
    print(f"[{tag}] round path {r['path']} (expected {expect_path}), "
          f"shards {r['n_shards']}, tpu_custom_call in compiled superstep: "
          f"{r['custom_call']}")
    print(f"[{tag}] compile {r['compile_s']:.3f} s, run {r['run_s']:.3f} s, "
          f"compiles inside the run window: {r['compiles']}")
    print(f"[{tag}] events posted {r['posted']}, ingested {c['ingested']}, "
          f"processed {c['processed']}, emitted {c['emitted']}, "
          f"sink records {r['records'].shape[0]} "
          f"(terminal-sink latency records {r['latency_records']}), "
          f"STATS stream rows with non-zero aggregates {agg_rows}, "
          f"dropped overflow/spool/quota {c['dropped_overflow']}/"
          f"{c['dropped_spool']}/{c['dropped_quota']}")
    check(r["path"] == expect_path, f"{tag}: round path {r['path']}")
    check(r["custom_call"] == expect_kernel,
          f"{tag}: tpu_custom_call presence {r['custom_call']}")
    check(r["compiles"] == 0, f"{tag}: {r['compiles']} compiles in window")
    check(r["records"].shape[0] > 0, f"{tag}: no sink records")
    check(agg_rows > 0, f"{tag}: STATS aggregates all zero")


def same(a: dict, b: dict, what: str, *, by_round: bool = True) -> None:
    """Bitwise agreement of two runs: sink records in emission order with
    their round, or (``by_round=False``) as a multiset of (sid, vals bits,
    ts, its); final values/timestamps; STATS aggregates."""
    ra, rb = a["records"], b["records"]
    fields = "sid, vals bits, ts, its, round"
    if not by_round:
        moved = int((np.sort(ra[:, 3]) != np.sort(rb[:, 3])).sum()) \
            if ra.shape == rb.shape else -1
        ra, rb = (np.delete(r, 3, axis=1) for r in (ra, rb))
        ra, rb = (r[np.lexsort(r.T[::-1])] for r in (ra, rb))
        fields = (f"sid, vals bits, ts, its; emission-round multisets "
                  f"differ at {moved} positions")
    check(ra.shape == rb.shape and np.array_equal(ra, rb),
          f"{what}: sink records differ")
    for key in ("values", "timestamps"):
        check(np.array_equal(a[key], b[key]), f"{what}: final {key} differ")
    for key in a["aggregates"]:
        check(np.array_equal(a["aggregates"][key], b["aggregates"][key]),
              f"{what}: STATS {key} aggregates differ")
    print(f"{what}: {ra.shape[0]} sink records ({fields}), final "
          f"values/timestamps and STATS aggregates bitwise equal; counters "
          f"equal: {a['counters'] == b['counters']}")


def sizes(r: dict) -> str:
    cfg = r["cfg"]
    return (f"config: {N_TENANTS} tenants {'/'.join(KINDS)}, n_streams "
            f"{cfg.n_streams}, batch {cfg.batch}, queue {cfg.queue}, "
            f"max_in {cfg.max_in}, max_out {cfg.max_out}, work lanes "
            f"{cfg.work}, superstep K={K}, trace {ROUNDS} rounds "
            f"({ROUNDS // WINDOW} STATS windows of {WINDOW}), bursts on; "
            f"PRED left out (its model needs a published-width config)")


def one_chip(seed: int) -> None:
    fused = run_suite(seed, fused=True)
    print(sizes(fused))
    report("fused", fused, "fused", True)
    ref = run_suite(seed, fused=False, scheduler="lexsort")
    report("xla-ref", ref, "staged", False)
    same(fused, ref, "fused vs staged/lexsort on one chip")


def four_chips(seed: int) -> None:
    sharded = run_suite(seed, fused=True, n_shards=4)
    print(sizes(sharded))
    report("4-shard", sharded, "fused", True)
    single = run_suite(seed, fused=True, n_streams=sharded["cfg"].n_streams)
    report("1-device", single, "fused", True)
    # ``batch`` is per shard: four shards pop up to 4x as many events a
    # round, so under backlog an emission can land in an earlier round
    same(sharded, single, "4 shards vs 1 device", by_round=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX sees {devs[0].platform} devices", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} but {len(devs)} TPU devices",
              file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {devs[0].device_kind}, count {len(devs)}")
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
