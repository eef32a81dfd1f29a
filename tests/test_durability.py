"""Durability & replay plane: a kill-and-resume from a checkpoint must be
bit-identical to the uninterrupted run (single-device AND sharded), the
retention ring must replay history to late joiners before live data, the
dead-letter spool must capture every drop class for drain/redelivery, and
none of it may retrace the compiled step on the steady-state path."""
import os

import numpy as np
import pytest

import jax
from jax import monitoring
from repro.launch.compiles import compile_count

from repro.checkpoint.ckpt import CheckpointManager, latest_step
from repro.core import (EngineConfig, Registry, create_engine,
                        restore_engine)

N_DEV = len(jax.devices())

# every (re)trace of any jitted function appends an event here
_TRACES = []
monitoring.register_event_duration_secs_listener(
    lambda name, dur, **kw: _TRACES.append(name)
    if name.startswith("/jax/core/compile") else None)


def _require(n_shards):
    if N_DEV < n_shards:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV}")


def _cfg(**kw):
    base = dict(n_streams=16, n_tenants=4, batch=8, queue=64, max_in=4,
                max_out=4, prog_len=24, n_temps=12,
                retention_slots=6, dlq_slots=16)
    base.update(kw)
    return EngineConfig(**base)


def _build(cfg):
    """Deterministic multi-hop topology; identical between calls so two
    engines start bit-identical."""
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    srcs = [reg.create_stream(t, f"s{i}", ["v"]) for i in range(4)]
    comps = [
        reg.create_composite(t, "c0", ["v"], [srcs[0]], {"v": "in0.v + 1"}),
        reg.create_composite(t, "c1", ["v"], [srcs[0], srcs[1]],
                             {"v": "in0.v + in1.v * 2"}),
        reg.create_composite(t, "c2", ["v"], [srcs[2]], {"v": "in0.v * 3"},
                             post_filter="out.v < 1e6"),
    ]
    comps.append(reg.create_composite(t, "c3", ["v"], [comps[0], comps[1]],
                                      {"v": "in0.v - in1.v"}))
    return reg, srcs, comps, create_engine(reg)


def _post_wave(eng, srcs, wave, base_ts):
    for i, s in enumerate(srcs):
        eng.post(s, [float(10 * wave + i)], base_ts)
    eng.post(srcs[0], [float(wave)], base_ts + 1)
    eng.post(srcs[2], [float(100 + wave)], base_ts + 2)


def _state_dict(eng):
    st = eng.state
    out = {f: np.asarray(getattr(st, f))
           for f in type(st)._fields if f != "stats"}
    out.update({f"stat.{k}": np.asarray(v) for k, v in st.stats.items()})
    return out


def _assert_same_state(a, b):
    da, db = _state_dict(a), _state_dict(b)
    assert set(da) == set(db)
    for k in da:
        np.testing.assert_array_equal(da[k], db[k], err_msg=k)


def _assert_same_sinks(sa, sb):
    assert len(sa) == len(sb)
    for x, y in zip(sa, sb):
        for f, u, v in zip(x._fields, x, y):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                          err_msg=f)


# --------------------------------------------------------------------------
# tentpole (a): kill-and-resume differential, 1 and 2 shards
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("K", [1, 3])
def test_kill_and_resume_bit_identical(tmp_path, n_shards, K):
    """Run two identical engines; checkpoint one mid-flight, destroy it,
    restore from disk, and continue both with identical input.  Every
    state leaf, stat and sink readback must match bit-for-bit."""
    _require(n_shards)
    cfg = _cfg(n_shards=n_shards, superstep=K)
    _, srcsA, _, engA = _build(cfg)
    _, srcsB, _, engB = _build(cfg)

    ts = 1
    for w in range(3):                       # phase 1: identical prefixes
        _post_wave(engA, srcsA, w, ts)
        _post_wave(engB, srcsB, w, ts)
        ts += 4
        for eng in (engA, engB):
            if K == 1:
                eng.round()
            else:
                eng.superstep(K)

    mgr = CheckpointManager(str(tmp_path), keep=2)
    arrays, meta = engA.snapshot()
    mgr.save_sync(engA._steps_done, arrays, extra=meta)
    del engA                                 # the crash

    engR = restore_engine(str(tmp_path))
    assert engR is not None
    assert type(engR).__name__ == ("ShardedStreamEngine" if n_shards > 1
                                   else "StreamEngine")
    _assert_same_state(engR, engB)           # resume point == survivor

    srcsR = [engR.registry.streams[s.sid] for s in srcsB]
    sinksR, sinksB = [], []
    for w in range(3, 6):                    # phase 2: identical suffixes
        _post_wave(engR, srcsR, w, ts)
        _post_wave(engB, srcsB, w, ts)
        ts += 4
        if K == 1:
            sinksR.append(engR.round())
            sinksB.append(engB.round())
        else:
            sinksR += engR.spool_sinks(engR.superstep(K), K)
            sinksB += engB.spool_sinks(engB.superstep(K), K)
    for eng, sinks in ((engR, sinksR), (engB, sinksB)):
        sinks += eng.drain()
    _assert_same_state(engR, engB)
    _assert_same_sinks(sinksR, sinksB)


# --------------------------------------------------------------------------
# tentpole (a): cadence + async manager + zero retraces after warmup
# --------------------------------------------------------------------------

def test_checkpoint_every_cadence(tmp_path):
    cfg = _cfg(checkpoint_every=2)
    _, srcs, _, eng = _build(cfg)
    mgr = eng.checkpoint_to(str(tmp_path), keep=2)
    ts = 1
    for w in range(6):
        _post_wave(eng, srcs, w, ts)
        ts += 4
        eng.round()
    mgr.wait()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    assert steps == [4, 6]                   # every 2 boundaries, keep 2
    engR = restore_engine(mgr)
    assert engR._steps_done == 6
    # the restored engine keeps counting from the restored boundary
    engR.checkpoint_to(str(tmp_path), keep=2).wait()
    engR.round()
    engR.round()
    engR._ckpt.wait()
    assert latest_step(str(tmp_path)) == 8


@pytest.mark.parametrize("n_shards", [1, 2])
def test_durability_ops_zero_retrace(n_shards):
    """After one warmup of each op, snapshot / replay / redeliver cycles
    must never retrace the compiled step or the requeue edits."""
    _require(n_shards)
    cfg = _cfg(n_shards=n_shards)
    _, srcs, comps, eng = _build(cfg)
    ts = 1
    for w in range(2):
        _post_wave(eng, srcs, w, ts)
        ts += 4
        eng.round()
    eng.drain()
    # warm every durability op once
    eng.snapshot()
    late = eng.admit_composite(eng.registry.tenants[0], "late", ["v"],
                               [srcs[3]], {"v": "in0.v"})
    eng.admit_subscription(late, srcs[0], replay=True)
    eng.redeliver()
    eng.revoke_stream(late)
    eng.dead_letters()
    eng.drain()

    cache0 = compile_count(eng._step)
    jax.block_until_ready(eng.state.timestamps)
    n_traces = len(_TRACES)
    for w in range(3):                       # steady-state churn
        eng.snapshot()
        late2 = eng.admit_composite(eng.registry.tenants[0], f"l{w}", ["v"],
                                    [srcs[3]], {"v": "in0.v * 2"})
        eng.admit_subscription(late2, srcs[1], replay=True)
        _post_wave(eng, srcs, w + 4, ts)
        ts += 4
        eng.drain()
        eng.redeliver()
        eng.revoke_stream(late2)
        eng.dead_letters()
    jax.block_until_ready(eng.state.timestamps)
    assert compile_count(eng._step) == cache0
    assert len(_TRACES) == n_traces


# --------------------------------------------------------------------------
# tentpole (b): retention ring replay to late joiners
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2])
def test_replay_catches_up_late_joiner(n_shards):
    _require(n_shards)
    cfg = _cfg(n_shards=n_shards)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    s1 = reg.create_stream(t, "s1", ["v"])
    eng = create_engine(reg)
    for i in range(4):
        eng.post(s0, [float(i)], ts=i + 1)
    eng.drain()

    late = eng.admit_composite(t, "late", ["v"], [s1], {"v": "in0.v"})
    assert eng.admit_subscription(late, s0, replay=True)
    eng.swap_program(late, {"v": "in0.v + in1.v * 2"})
    eng.drain()
    c = eng.counters()
    assert c["replayed"] == 4                # full history re-enqueued
    assert eng.ts_of(late) == 4              # caught up to newest
    assert eng.value_of(late)[0] == 6.0      # 0 + 3*2

    # live data after the catch-up flows normally
    eng.post(s0, [10.0], ts=9)
    eng.drain()
    assert eng.value_of(late)[0] == 20.0 and eng.ts_of(late) == 9


def test_retention_ring_keeps_newest_window():
    """More emissions than slots: a late joiner sees exactly the last
    ``retention_slots`` SUs, oldest-first."""
    cfg = _cfg(retention_slots=3)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    s1 = reg.create_stream(t, "s1", ["v"])
    eng = create_engine(reg)
    for i in range(8):                       # 8 > 3 slots: ring wraps
        eng.post(s0, [float(i)], ts=i + 1)
    eng.drain()
    late = eng.admit_composite(t, "late", ["v"], [s1], {"v": "in0.v"})
    eng.admit_subscription(late, s0, replay=True)
    q_ts = sorted(int(tsv) for tsv, v in
                  zip(np.atleast_2d(np.asarray(eng.state.q_ts)).ravel(),
                      np.atleast_2d(np.asarray(eng.state.q_valid)).ravel())
                  if v)
    assert q_ts == [6, 7, 8]                 # newest window only
    eng.drain()
    assert eng.counters()["replayed"] == 3


def test_replay_without_retention_is_noop():
    cfg = _cfg(retention_slots=0)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    s1 = reg.create_stream(t, "s1", ["v"])
    eng = create_engine(reg)
    eng.post(s0, [1.0], ts=1)
    eng.drain()
    late = eng.admit_composite(t, "late", ["v"], [s1], {"v": "in0.v"})
    assert eng.admit_subscription(late, s0, replay=True)
    assert eng.counters()["replayed"] == 0


# --------------------------------------------------------------------------
# tentpole (c): dead-letter spool per drop class + redelivery
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2])
def test_dlq_captures_revoked_queue_purge(n_shards):
    _require(n_shards)
    cfg = _cfg(n_shards=n_shards)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    mid = reg.create_composite(t, "mid", ["v"], [s0], {"v": "in0.v"})
    end = reg.create_composite(t, "end", ["v"], [mid], {"v": "in0.v + 1"})
    eng = create_engine(reg)
    eng.post(s0, [7.0], ts=50)
    eng.round()                              # mid emitted; queued for end
    assert bool(np.asarray(eng.state.q_valid).any())
    eng.revoke_stream(mid)
    letters = eng.dead_letters(clear=False)
    assert [(l.sid, l.reason, l.ts, float(l.vals[0]), l.tenant)
            for l in letters] == [(mid.sid, "revoked", 50, 7.0, 0)]
    # redelivery refuses the dead sid — the letter *stays* in the spool
    # (re-appended, original reason preserved) and the refusal is counted
    assert eng.redeliver() == 0
    assert eng.counters()["redeliver_rejected"] == 1
    kept = eng.dead_letters(clear=False)
    assert [(l.sid, l.reason, l.ts, float(l.vals[0]), l.tenant)
            for l in kept] == [(mid.sid, "revoked", 50, 7.0, 0)]


def test_dlq_captures_revoked_ingest():
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    s1 = reg.create_stream(t, "s1", ["v"])
    eng = create_engine(reg)
    eng.post(s0, [9.0], ts=60)               # pending host-side
    eng.revoke_stream(s0)                    # row dies before ingest
    eng.round()
    letters = eng.dead_letters()
    assert [(l.reason, l.ts) for l in letters] == [("revoked", 60)]


def test_dlq_captures_quota_shed_and_redelivers():
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t0 = reg.create_tenant("t0")
    srcs = [reg.create_stream(t0, f"s{i}", ["v"]) for i in range(3)]
    eng = create_engine(reg)
    eng.set_quota(t0, 1)                     # 1 SU/round, burst 1
    for i, s in enumerate(srcs):
        eng.post(s, [float(i)], ts=5)
    eng.round()
    assert eng.counters()["dropped_quota"] == 2
    letters = eng.dead_letters(clear=False)
    assert sorted(l.reason for l in letters) == ["quota", "quota"]
    assert all(l.tenant == 0 for l in letters)
    # quota letters re-enter ingest admission: with the quota lifted,
    # both store at their rows and fan out like a fresh post
    eng.set_quota(t0, 0)
    assert eng.redeliver() == 2
    eng.drain()
    assert eng.counters()["dropped_quota"] == 2      # no re-shed
    for l in letters:
        assert eng.ts_of(l.sid) == l.ts
        assert eng.value_of(l.sid)[0] == l.vals[0]


def test_dlq_captures_spool_overflow():
    cfg = _cfg(superstep=4, sink_spool_slots=2)
    _, srcs, _, eng = _build(cfg)
    ts = 1
    for w in range(3):
        _post_wave(eng, srcs, w, ts)
        ts += 4
    while eng._pending or bool(np.asarray(eng.state.q_valid).any()):
        eng.superstep(4)
    c = eng.counters()
    assert c["dropped_spool"] > 0
    letters = eng.dead_letters()
    assert sum(l.reason == "spool" for l in letters) == \
        min(c["dropped_spool"], cfg.dlq_slots)


def test_dlq_survives_snapshot_restore():
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    mid = reg.create_composite(t, "mid", ["v"], [s0], {"v": "in0.v"})
    end = reg.create_composite(t, "end", ["v"], [mid], {"v": "in0.v"})
    eng = create_engine(reg)
    eng.post(s0, [7.0], ts=50)
    eng.round()
    eng.revoke_stream(mid)
    engR = restore_engine(eng.snapshot())
    assert [(l.sid, l.reason) for l in engR.dead_letters()] == \
        [(mid.sid, "revoked")]


def test_dlq_off_is_pure_noop():
    """dlq_slots=0: drops are counted but no spool exists — and the
    state pytree stays numerically identical to the pre-DLQ layout."""
    cfg = _cfg(dlq_slots=0)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    mid = reg.create_composite(t, "mid", ["v"], [s0], {"v": "in0.v"})
    end = reg.create_composite(t, "end", ["v"], [mid], {"v": "in0.v"})
    eng = create_engine(reg)
    eng.post(s0, [7.0], ts=50)
    eng.round()
    eng.revoke_stream(mid)
    assert eng.counters()["dropped_revoked"] == 1
    assert eng.dead_letters() == []
    assert eng.redeliver() == 0


# --------------------------------------------------------------------------
# serving bridge control-state round-trip
# --------------------------------------------------------------------------

class _StubBatcher:
    """Just enough surface for the bridge's control plane — the snapshot
    round-trip never decodes."""

    class cfg:
        vocab = 64

    def submit(self, req):
        raise AssertionError("snapshot test should not submit")

    def run_ticks(self, n):
        return []


def test_bridge_snapshot_restore():
    import json

    from repro.serving.bridge import ModelBackedStreams

    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    src = reg.create_stream(t, "src", ["v"])
    eng = create_engine(reg)
    batcher = _StubBatcher()
    bridge = ModelBackedStreams(eng, batcher)
    pair = bridge.admit_route(t, "scorer", [src])
    assert pair is not None
    model, resp = pair
    bridge.deferred.append((model.sid, np.ones((cfg.channels,),
                                               np.float32), 3))
    bridge._next_rid = 5

    snap = json.loads(json.dumps(bridge.snapshot()))   # survives JSON
    engR = restore_engine(eng.snapshot())
    bridge2 = ModelBackedStreams(engR, batcher)
    bridge2.restore(snap)
    assert bridge2._next_rid == 5
    assert list(bridge2.routes) == [model.sid]
    r = bridge2.routes[model.sid]
    assert r.response_stream.sid == resp.sid
    assert len(bridge2.deferred) == 1 and bridge2.deferred[0][0] == model.sid
