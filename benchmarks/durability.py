"""Durability & replay plane — checkpoint, restore, replay and DLQ costs.

The headline claims of the durability plane (ISSUE 6): a kill-and-resume
from a checkpoint is *bit-identical* to the uninterrupted run, and none of
the durability operations — snapshot, checkpointed rounds, retention
replay to late joiners, dead-letter drain/redelivery — retrace the
compiled step on the steady-state path.  This benchmark builds a mid-size
multi-hop topology under continuous load and measures:

  * ``snapshot_ms`` / ``save_sync_ms`` / ``restore_ms`` — host latency of
    a device->host state capture, a full fsync-barrier checkpoint write,
    and a cold ``restore_engine`` (registry rebuild + table upload);
  * ``restore_identical``      — after restoring mid-flight and feeding
    the original and restored engines identical input, every state leaf
    and stat matches bit-for-bit (the benchmark exits non-zero if not);
  * ``rounds_per_s`` off/on    — loaded rounds/s without checkpointing vs
    with ``checkpoint_every=K`` async checkpoints riding the round loop;
    ``overhead_pct`` is the cost of durability in the hot path;
  * ``replay_ms``              — host latency of one
    ``admit_subscription(..., replay=True)`` catch-up (retention ring
    drain -> jitted requeue), measured over live churn;
  * ``redeliver_ms``           — dead-letter drain + redelivery latency;
  * ``retraces``               — compiled-step cache growth over the whole
    churn tail (snapshot + replay + revoke + redeliver every cycle); the
    contract, as everywhere in this repo, is **0**.

Run ``python -m benchmarks.durability [--rounds R] [--shards S]
[--checkpoint-every K] [--json PATH] [--smoke]``.  ``--smoke`` is the CI
mode (tiny topology, few rounds; latency numbers are not meaningful but
the retrace and bit-identity contracts are enforced).  JSON schema:
benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

if __package__ in (None, ""):  # `python benchmarks/durability.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402

from repro.checkpoint.ckpt import CheckpointManager           # noqa: E402
from repro.core import (EngineConfig, Registry, create_engine,  # noqa: E402
                        restore_engine)
from repro.launch.compiles import (compile_count,              # noqa: E402
                                   use_compile_cache)


def _build(n_chains: int, depth: int, n_shards: int, checkpoint_every: int):
    """``n_chains`` source->composite chains of ``depth`` hops plus one
    shared 2-input join per pair of chains — enough cross-stream edges to
    exercise retention, fanout and the exchange."""
    n_nodes = n_chains * (1 + depth) + n_chains // 2 + 4
    cfg = EngineConfig(
        n_streams=n_nodes, n_tenants=4, batch=16, queue=4 * 16,
        max_in=2, max_out=4, prog_len=24, n_temps=12, n_shards=n_shards,
        retention_slots=8, dlq_slots=32, checkpoint_every=checkpoint_every,
    )
    reg = Registry.with_capacity(cfg, max_streams=n_nodes + 8)
    t = reg.create_tenant("t", quota_streams=10 ** 9)
    srcs = [reg.create_stream(t, f"s{i}", ["v"]) for i in range(n_chains)]
    tails = []
    for i, s in enumerate(srcs):
        node = s
        for d in range(depth):
            node = reg.create_composite(t, f"c{i}_{d}", ["v"], [node],
                                        {"v": f"in0.v + {d + 1}"})
        tails.append(node)
    for i in range(0, n_chains - 1, 2):
        reg.create_composite(t, f"j{i}", ["v"], [tails[i], tails[i + 1]],
                             {"v": "in0.v + in1.v * 2"})
    return cfg, reg, t, srcs


def _state_fingerprint(eng):
    st = eng.state
    out = {f: np.asarray(getattr(st, f))
           for f in type(st)._fields if f != "stats"}
    out.update({f"stat.{k}": np.asarray(v) for k, v in st.stats.items()})
    return out


def _identical(a, b) -> bool:
    fa, fb = _state_fingerprint(a), _state_fingerprint(b)
    return set(fa) == set(fb) and all(
        np.array_equal(fa[k], fb[k]) for k in fa)


def _wave(eng, srcs, r, ts):
    for i, s in enumerate(srcs):
        eng.post(s, [float(r + i)], ts)


def bench(rounds: int, n_chains: int, depth: int, n_shards: int,
          checkpoint_every: int, workdir: str):
    cfg, reg, tenant, srcs = _build(n_chains, depth, n_shards,
                                    checkpoint_every)
    eng = create_engine(reg)
    ts = 1

    # ---- warm-up: trace the round and every durability op once
    _wave(eng, srcs, 0, ts); ts += 2
    eng.round()
    eng.snapshot()
    late = eng.admit_composite(tenant, "w_late", ["v"], [srcs[1]],
                               {"v": "in0.v"})
    eng.admit_subscription(late, srcs[0], replay=True)
    eng.revoke_stream(late)
    eng.redeliver()
    eng.drain()
    jax.block_until_ready(eng.state.timestamps)
    cache0 = compile_count(eng._step)

    # ---- timed: plain loaded rounds vs checkpointed loaded rounds
    def timed_rounds(n):
        nonlocal ts
        t0 = time.perf_counter()
        for r in range(n):
            _wave(eng, srcs, r, ts); ts += 2
            eng.round()
        jax.block_until_ready(eng.state.timestamps)
        return n / (time.perf_counter() - t0)

    plain_rps = timed_rounds(rounds)    # manager detached: no snapshots
    eng.checkpoint_to(os.path.join(workdir, "ring"), keep=2)
    ckpt_rps = timed_rounds(rounds)
    eng.checkpoint_to(None)             # detach: back to plain rounds

    # ---- snapshot / save / restore latency + bit-identity differential
    t0 = time.perf_counter()
    arrays, meta = eng.snapshot()
    snapshot_ms = 1e3 * (time.perf_counter() - t0)
    mgr = CheckpointManager(os.path.join(workdir, "cold"), keep=1)
    t0 = time.perf_counter()
    mgr.save_sync(eng._steps_done, arrays, extra=meta)
    save_ms = 1e3 * (time.perf_counter() - t0)
    t0 = time.perf_counter()
    engR = restore_engine(mgr)
    restore_ms = 1e3 * (time.perf_counter() - t0)
    tsR = ts
    for r in range(3):                  # identical continuation on both
        _wave(eng, srcs, 99 + r, ts); ts += 2
        eng.round()
        _wave(engR, [s for s in srcs], 99 + r, tsR); tsR += 2
        engR.round()
    eng.drain(); engR.drain()
    restore_identical = _identical(eng, engR)

    # ---- churn tail: replay + DLQ cycles under load (zero retraces)
    replay_ms, redeliver_ms = [], []
    jax.block_until_ready(eng.state.timestamps)
    for r in range(max(rounds // 4, 3)):
        lname = f"late{r}"
        comp = eng.admit_composite(tenant, lname, ["v"], [srcs[r % 2 + 1]],
                                   {"v": "in0.v * 2"})
        t0 = time.perf_counter()
        eng.admit_subscription(comp, srcs[0], replay=True)
        replay_ms.append(1e3 * (time.perf_counter() - t0))
        _wave(eng, srcs, r, ts); ts += 2
        eng.round()
        eng.revoke_stream(comp)         # purged SUs dead-letter (revoked)
        t0 = time.perf_counter()
        eng.redeliver()
        redeliver_ms.append(1e3 * (time.perf_counter() - t0))
        eng.drain()
    jax.block_until_ready(eng.state.timestamps)
    retraces = compile_count(eng._step) - cache0

    c = eng.counters()
    return {
        "config": {"rounds": rounds, "chains": n_chains, "depth": depth,
                   "n_shards": n_shards,
                   "checkpoint_every": checkpoint_every,
                   "retention_slots": cfg.retention_slots,
                   "dlq_slots": cfg.dlq_slots,
                   "platform": jax.devices()[0].platform},
        "snapshot_ms": snapshot_ms,
        "save_sync_ms": save_ms,
        "restore_ms": restore_ms,
        "restore_identical": bool(restore_identical),
        "rounds_per_s": {"plain": plain_rps, "checkpointed": ckpt_rps},
        "overhead_pct": 100.0 * (1.0 - ckpt_rps / plain_rps),
        "replay_ms": {"mean": float(np.mean(replay_ms)),
                      "p50": float(np.median(replay_ms)),
                      "max": float(np.max(replay_ms))},
        "redeliver_ms": {"mean": float(np.mean(redeliver_ms)),
                         "p50": float(np.median(redeliver_ms)),
                         "max": float(np.max(redeliver_ms))},
        "replayed": int(c["replayed"]),
        "dropped_revoked": int(c["dropped_revoked"]),
        "retraces": retraces,
    }


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--chains", type=int, default=8)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--checkpoint-every", type=int, default=4)
    ap.add_argument("--json", default=None, help="write results as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny topology, few rounds")
    args = ap.parse_args()
    if args.smoke:
        args.rounds, args.chains, args.depth = 8, 4, 2

    workdir = tempfile.mkdtemp(prefix="bench_durability_")
    try:
        res = bench(args.rounds, args.chains, args.depth, args.shards,
                    args.checkpoint_every, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    rps = res["rounds_per_s"]
    print(f"snapshot {res['snapshot_ms']:7.2f} ms   "
          f"save(sync) {res['save_sync_ms']:7.2f} ms   "
          f"restore {res['restore_ms']:8.2f} ms")
    print(f"rounds/s   plain {rps['plain']:8.1f}   "
          f"checkpointed(K={res['config']['checkpoint_every']}) "
          f"{rps['checkpointed']:8.1f}   overhead {res['overhead_pct']:+.1f}%")
    print(f"replay    mean {res['replay_ms']['mean']:6.2f} ms   "
          f"redeliver mean {res['redeliver_ms']['mean']:6.2f} ms   "
          f"(replayed {res['replayed']}, revoked-drops "
          f"{res['dropped_revoked']})")
    print(f"restore bit-identical: {res['restore_identical']}   "
          f"retraces during durability churn: {res['retraces']} "
          "(contracts: True / 0)")
    if args.json:        # write the artifact even (especially) on failure
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if res["retraces"]:
        print("WARNING: durability ops caused recompilation",
              file=sys.stderr)
        sys.exit(1)
    if not res["restore_identical"]:
        print("WARNING: restored engine diverged from the survivor",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
