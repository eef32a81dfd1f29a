"""Chaos soak for the elastic plane (slow tier).

~200 randomized supersteps of admit / revoke / set_weight / set_quota /
checkpoint / resize / redeliver churn at 1-4 shards, asserting after every
boundary that

  * XLA compiles happen ONLY at resize boundaries (the zero-retrace churn
    contract survives arbitrary interleaving — a warm twin engine
    pre-compiles every shape-keyed global jit first, so the counter
    isolates the soak engine's own programs);
  * SU accounting is conserved: ``queued_in == popped + purged + queue
    occupancy`` exactly, across every migration;
  * and at the end, the final snapshot restores bit-identically at the
    final count AND across counts.
"""
import numpy as np
import pytest

import jax

from repro.core import (EngineConfig, Registry, create_engine,
                        restore_engine)
from repro.launch.compiles import compile_count

N_DEV = len(jax.devices())


SHARD_LEVELS = (1, 2, 4)
K = 2


def _cfg():
    return EngineConfig(n_streams=12, n_tenants=4, batch=4, queue=32,
                        max_in=4, max_out=4, prog_len=24, n_temps=12,
                        retention_slots=4, dlq_slots=8, superstep=K,
                        checkpoint_every=7)


def _build():
    reg = Registry.with_capacity(_cfg())
    tens = [reg.create_tenant(f"t{i}") for i in range(3)]
    srcs = [reg.create_stream(tens[i], f"s{i}", ["v"]) for i in range(3)]
    comps = []
    for i, a in enumerate(srcs):              # chains keep SUs in flight
        b = reg.create_composite(tens[i], f"b{i}", ["v"], [a],
                                 {"v": "in0.v + 1"})
        comps.append(reg.create_composite(tens[i], f"c{i}", ["v"], [b],
                                          {"v": "in0.v * 2"}))
    return tens, srcs, comps, create_engine(reg)


def _churn(eng, tens, srcs, rng, ts, admitted):
    """One iteration's random churn (everything but resize), via the same
    public API an operator would use."""
    for _ in range(rng.randint(1, 5)):
        eng.post(srcs[rng.randint(len(srcs))], [float(rng.randint(100))], ts)
        ts += 1
    op = rng.randint(6)
    if op == 0:
        s = eng.admit_stream(tens[rng.randint(3)], f"x{ts}", ["v"])
        if s is not None:
            admitted.append(s)
    elif op == 1 and admitted:
        eng.revoke_stream(admitted.pop(rng.randint(len(admitted))))
    elif op == 2:
        eng.set_weight(tens[rng.randint(3)], 1 + rng.randint(4))
    elif op == 3:
        eng.set_quota(tens[rng.randint(3)], 1 + rng.randint(8))
    elif op == 4:
        eng.redeliver()
    # op == 5: pure posting iteration
    return ts


def _assert_conserved(eng, where):
    c = eng.counters()
    occ = int(np.asarray(eng.state.q_valid).sum())
    assert c["queued_in"] == c["popped"] + c["purged"] + occ, \
        f"{where}: queued_in={c['queued_in']} popped={c['popped']} " \
        f"purged={c['purged']} occ={occ}"


@pytest.mark.slow
def test_chaos_soak(tmp_path):
    if N_DEV < max(SHARD_LEVELS):
        pytest.skip(f"needs {max(SHARD_LEVELS)} devices, have {N_DEV}")

    # ---- warm every shape-keyed global jit with a twin -----------------
    # deterministic, not sampled: every churn op runs once at every shard
    # count, so the soak's compile counter sees only the soak engine's own
    # per-resize program
    tens, srcs, _, twin = _build()
    twin.checkpoint_to(str(tmp_path / "warm"))
    ts = 1
    for i in range(3):                        # retention history for replay
        twin.post(srcs[0], [float(i)], ts)
        ts += 1
        twin.drain()
    for n in (1, 2, 4, 2, 1):
        twin.resize(n)
        x = twin.admit_stream(tens[0], f"wx{n}.{ts}", ["v"])
        twin.post(srcs[0], [float(ts)], ts)
        ts += 1
        twin.superstep(K)
        twin.set_weight(tens[0], 2)
        twin.set_quota(tens[1], 3)
        if x is not None:
            twin.post(x, [9.0], ts)           # queued SU -> revoke letter
            ts += 1
            twin.revoke_stream(x)
        late = twin.admit_composite(tens[0], f"wl{n}.{ts}", ["v"],
                                    [srcs[1]], {"v": "in0.v"})
        twin.admit_subscription(late, srcs[0], replay=True)  # warms requeue
        twin.revoke_stream(late)
        twin.redeliver()                      # warms the DLQ drain + clear
        twin.snapshot()
        twin.superstep(K)
    jax.block_until_ready(twin.state.timestamps)
    twin._ckpt.wait()
    twin.checkpoint_to(None)

    # ---- the soak proper ----------------------------------------------
    tens, srcs, _, eng = _build()
    eng.checkpoint_to(str(tmp_path / "soak"))
    rng = np.random.RandomState(42)
    admitted, ts = [], 1
    eng.superstep(K)                          # own closure: first compile
    jax.block_until_ready(eng.state.timestamps)

    resizes = 0
    for step in range(200):
        resized = rng.rand() < 0.08
        before = compile_count()
        if resized:
            n_now = eng.cfg.n_shards
            choices = [n for n in SHARD_LEVELS if n != n_now]
            eng.resize(choices[rng.randint(len(choices))])
            resizes += 1
        ts = _churn(eng, tens, srcs, rng, ts, admitted)
        eng.superstep(K)
        jax.block_until_ready(eng.state.timestamps)
        compiled = compile_count() - before
        if resized:
            assert compiled <= 1, \
                f"step {step}: resize cost {compiled} compiles (max 1)"
        else:
            assert compiled == 0, \
                f"step {step}: {compiled} compiles outside a resize boundary"
        _assert_conserved(eng, f"step {step} ({eng.cfg.n_shards} shards)")
    assert resizes >= 5, "soak never exercised resize enough"

    # ---- final state restores bit-identically --------------------------
    eng._ckpt.wait()
    snap = eng.snapshot()
    # same-count restore: every leaf bit-for-bit
    aa, ab = snap[0], restore_engine(snap).snapshot()[0]
    assert sorted(aa) == sorted(ab)
    for k in sorted(aa):
        np.testing.assert_array_equal(aa[k], ab[k], err_msg=k)
    # cross-count roundtrips: resharding renormalizes the queue's slot
    # packing and seq numbering (order-preserving), so queue bookkeeping
    # is compared order-canonically and everything else bit-for-bit
    _QKEYS = {"state/q_sid", "state/q_vals", "state/q_ts", "state/q_seq",
              "state/q_valid", "state/seq"}

    def queue_canon(arrays):
        sid = arrays["state/q_sid"]
        vals = arrays["state/q_vals"]
        ts = arrays["state/q_ts"]
        seq = arrays["state/q_seq"]
        valid = arrays["state/q_valid"]
        if sid.ndim == 1:
            sid, vals, ts = sid[None], vals[None], ts[None]
            seq, valid = seq[None], valid[None]
        return [[(int(sid[s, i]), int(ts[s, i]), tuple(vals[s, i].tolist()))
                 for i in np.argsort(seq[s], kind="stable") if valid[s, i]]
                for s in range(sid.shape[0])]

    # stats/tenant counters live per-shard on the live engine but are
    # consolidated onto shard 0 by resharding: totals must be conserved;
    # quota token buckets are reset by policy on reshard
    _TOTAL_KEYS = {"state/tenant_emitted", "state/tenant_dropped_quota",
                   "state/tenant_dropped_overflow", "state/tenant_queued"}
    _RESET_KEYS = {"state/tokens"}
    for n_via in (1, 2):
        via = restore_engine(snap, n_shards=n_via)
        back = restore_engine(via.snapshot(), n_shards=eng.cfg.n_shards)
        ab = back.snapshot()[0]
        assert sorted(aa) == sorted(ab)
        for k in sorted(aa):
            if k in _QKEYS or k in _RESET_KEYS:
                continue
            if k.startswith("state/stats/"):
                assert aa[k].sum() == ab[k].sum(), f"via {n_via}: {k}"
            elif k in _TOTAL_KEYS:
                np.testing.assert_array_equal(
                    aa[k].sum(axis=0) if aa[k].ndim == 2 else aa[k],
                    ab[k].sum(axis=0) if ab[k].ndim == 2 else ab[k],
                    err_msg=f"via {n_via}: {k}")
            else:
                np.testing.assert_array_equal(aa[k], ab[k],
                                              err_msg=f"via {n_via}: {k}")
        assert queue_canon(aa) == queue_canon(ab), f"via {n_via}: queue order"
    # and the on-disk checkpoint is a valid recovery point
    engR = restore_engine(str(tmp_path / "soak"))
    _assert_conserved(engR, "restored from disk")
