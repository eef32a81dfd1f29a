"""Fault-isolation plane tests: the device circuit breaker, quarantine
semantics, checkpoint checksums with newest-valid fallback, and the
self-healing supervisor.

* a hypothesis property test drives the breaker's window state machine
  (poison / clean / idle rounds + host unquarantine) against a pure-python
  reference model, and checks the counters are conserved across
  snapshot/restore — including a cross-shard-count restore (the pinned
  fixed cases run even without hypothesis, same idiom as
  ``test_elastic_property.py``);
* a fused-vs-staged differential proves poison detection and quarantine
  are bitwise identical on both execution paths at 1 and 2 shards,
  K in {1, 3}, with zero retraces under quarantine/unquarantine churn;
* checkpoint tests tear real checkpoints with the chaos injectors and
  assert the checksum plane refuses them and falls back to the newest
  older valid step;
* supervisor tests recover from injected ``ShardKill``s (including with a
  torn newest checkpoint), assign blame from fault counters, and escalate
  repeat offenders to quarantine;
* a seeded 200-superstep chaos soak (slow tier) runs the whole story
  end-to-end against an undisturbed twin.
"""
import numpy as np
import pytest

import jax

from repro.core import EngineConfig, Registry, create_engine, restore_engine
from repro.checkpoint import ckpt
from repro.launch import chaos as C
from repro.launch.compiles import compile_count
from repro.launch.supervise import Supervisor, supervised_run

N_DEV = len(jax.devices())

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                          # pragma: no cover
    _HAVE_HYPOTHESIS = False


def _require(n_shards):
    if N_DEV < n_shards:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV}")


def _cfg(**kw):
    base = dict(n_streams=16, n_tenants=4, channels=1, batch=4, queue=32,
                max_in=4, max_out=4, prog_len=24, n_consts=8, n_temps=12,
                sink_buffer=8, retention_slots=2, dlq_slots=16)
    base.update(kw)
    return EngineConfig(**base).validate()


def _poison_rig(**kw):
    """One tenant, src -> comp (fusable transform): a NaN posted to src
    becomes a non-finite VM output charged to comp."""
    cfg = _cfg(**kw)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    src = reg.create_stream(t, "src", ["v"])
    comp = reg.create_composite(t, "comp", ["v"], [src],
                                {"v": "src.v * 2.0"})
    return create_engine(reg), src, comp


# --------------------------------------------------------------------------
# breaker state machine: property test vs a pure-python reference
# --------------------------------------------------------------------------

class _RefBreaker:
    """Host model of one row's breaker window machine (mirrors
    ``fault_events``/``fault_phase``): a fault at round ``rid`` restarts
    the window when it fell outside ``W`` rounds of the window's epoch (or
    the window is empty), a fault-free round past expiry decays the count
    to 0, it trips at ``count >= F`` while not yet quarantined, and
    ``unquarantine`` clears the window but not the lifetime total.  The
    clock is the engine's round counter (host edits run no round)."""

    def __init__(self, W, F):
        self.W, self.F = W, F
        self.count = 0
        self.epoch = 0
        self.total = 0
        self.quar = False

    def round(self, rid, fault):
        in_win = (rid - self.epoch) < self.W
        if not fault:
            if not in_win:
                self.count = 0
            return
        self.total += 1
        if not in_win or self.count == 0:
            self.epoch, self.count = rid, 1
        else:
            self.count += 1
        if self.F > 0 and self.count >= self.F and not self.quar:
            self.quar = True

    def unquarantine(self):
        self.quar = False
        self.count = 0
        self.epoch = 0


def _check_breaker_sequence(ops, W=4, F=2, cross_shard=False):
    eng, src, comp = _poison_rig(fault_window=W, fault_threshold=F)
    ref = _RefBreaker(W, F)
    row = comp.sid
    ts = 1
    rid = 0
    for op in ops:
        if op == "unq":
            eng.unquarantine(comp)
            ref.unquarantine()
            continue                          # host edit: no round
        if op == "poison":
            eng.post(src, [np.nan], ts=ts)
        elif op == "clean":
            eng.post(src, [1.0], ts=ts)
        ts += 1
        eng.round()
        ref.round(rid, op == "poison")
        rid += 1
    fc = eng.fault_counters()
    assert bool(fc["quarantined"][row]) == ref.quar, ops
    assert int(fc["fault_total"][row]) == ref.total, ops
    assert int(fc["fault_count"][row]) == ref.count, ops
    # every other row stayed silent
    mask = np.ones_like(fc["fault_total"], bool)
    mask[row] = False
    assert not fc["quarantined"][mask].any()
    assert fc["fault_total"][mask].sum() == 0
    # counters survive snapshot -> restore bit-for-bit
    snap = eng.snapshot()
    eng2 = restore_engine(snap)
    fc2 = eng2.fault_counters()
    for k in fc:
        np.testing.assert_array_equal(fc[k], fc2[k], err_msg=k)
    assert eng2.is_quarantined(comp) == ref.quar
    if cross_shard and N_DEV >= 2:
        # ...and across a shard-count change (restore is resize's oracle)
        eng3 = restore_engine(snap, n_shards=2)
        fc3 = eng3.fault_counters()
        for k in fc:
            np.testing.assert_array_equal(fc[k], fc3[k], err_msg=k)
        assert eng3.is_quarantined(comp) == ref.quar


# the named edge cases, pinned so they run even without hypothesis
def test_breaker_trips_at_threshold():
    _check_breaker_sequence(["poison", "poison", "poison"],
                            cross_shard=True)


def test_breaker_window_decay():
    # faults W rounds apart never accumulate: each restarts the window
    _check_breaker_sequence(
        ["poison"] + ["idle"] * 4 + ["poison"] + ["idle"] * 4 + ["poison"])


def test_breaker_unquarantine_resets_window_not_total():
    _check_breaker_sequence(
        ["poison", "poison", "unq", "clean", "poison"], cross_shard=True)


def test_breaker_disarmed_still_counts():
    eng, src, comp = _poison_rig(fault_window=8, fault_threshold=0)
    for i in range(3):
        eng.post(src, [np.nan], ts=i + 1)
        eng.round()
    fc = eng.fault_counters()
    assert int(fc["fault_total"][comp.sid]) == 3
    assert not fc["quarantined"].any()       # threshold=0: never trips


def test_breaker_resize_conserves_counters():
    _require(2)
    eng, src, comp = _poison_rig(fault_window=4, fault_threshold=2)
    for i in range(3):
        eng.post(src, [np.nan], ts=i + 1)
        eng.round()
    before = eng.fault_counters()
    assert bool(before["quarantined"][comp.sid])
    eng.resize(2)
    after = eng.fault_counters()
    for k in before:
        np.testing.assert_array_equal(before[k], after[k], err_msg=k)
    assert eng.is_quarantined(comp)
    eng.unquarantine(comp)
    assert not eng.is_quarantined(comp)
    assert int(eng.fault_counters()["fault_total"][comp.sid]) == 3


if _HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(st.lists(
        st.sampled_from(["poison", "clean", "idle", "unq"]),
        min_size=1, max_size=16))
    def test_breaker_state_machine_property(ops):
        _check_breaker_sequence(ops)


# --------------------------------------------------------------------------
# fused vs staged: poison detection is path-independent
# --------------------------------------------------------------------------

def _diff_build(fused: bool, n_shards: int, K: int):
    cfg = _cfg(n_streams=24, batch=6, fused_round=fused, n_shards=n_shards,
               superstep=K, fault_window=6, fault_threshold=2)
    reg = Registry.with_capacity(cfg)
    t0, t1 = reg.create_tenant("a"), reg.create_tenant("b")
    s0 = reg.create_stream(t0, "s0", ["v"])
    s1 = reg.create_stream(t1, "s1", ["v"])
    c0 = reg.create_composite(t0, "c0", ["v"], [s0], {"v": "s0.v * 2.0"})
    c1 = reg.create_composite(t1, "c1", ["v"], [s1], {"v": "s1.v + 1.0"})
    return create_engine(reg), (s0, s1, c0, c1)


def _diff_drive(eng, streams, K: int):
    """Poison bursts + quarantine/unquarantine churn, identical on both
    engines.  Returns the number of supersteps driven."""
    s0, s1, c0, c1 = streams
    rng = np.random.default_rng(5)
    n = 0
    for phase in range(3):
        for i in range(4):
            eng.post(s0, [np.nan if i % 2 == 0 else 1.5], ts=100 * phase + i)
            eng.post(s1, [float(rng.standard_normal())], ts=100 * phase + i)
            eng.superstep(K)
            n += 1
        if phase == 0:
            eng.quarantine(c1)               # host-forced trip
            eng.set_breaker(window=8)
        elif phase == 1:
            eng.unquarantine(c0)             # lift the auto-trip
            eng.unquarantine(c1)
    return n


def _state_arrays(eng):
    from repro.core.engine import EngineState
    out = {}
    for f in EngineState._fields:
        if f == "stats":
            for k, v in eng.state.stats.items():
                out[f"stats/{k}"] = np.asarray(v)
        else:
            out[f"state/{f}"] = np.asarray(getattr(eng.state, f))
    return out


@pytest.mark.parametrize("n_shards,K", [(1, 1), (1, 3), (2, 1), (2, 3)])
def test_fused_staged_poison_differential(n_shards, K):
    """Non-finite detection, breaker trips and quarantine purges are
    bitwise identical between the fused and staged rounds (float32
    compared in bit space so the NaN payloads count too), and the
    quarantine churn causes zero retraces on either path."""
    _require(n_shards)
    e0, st0 = _diff_build(False, n_shards, K)
    e1, st1 = _diff_build(True, n_shards, K)
    assert e0._path == "staged" and e1._path == "fused"
    scans = []                               # each engine's scan compiles
    for eng, st in ((e0, st0), (e1, st1)):
        scan0 = compile_count(eng._superstep_fn(K))
        _diff_drive(eng, st, K)
        scans.append(compile_count(eng._superstep_fns[K]) - scan0)
    a, b = _state_arrays(e0), _state_arrays(e1)
    assert a.keys() == b.keys()
    for k in a:
        x, y = a[k], b[k]
        assert x.shape == y.shape, k
        np.testing.assert_array_equal(
            x.view(np.int32) if x.dtype == np.float32 else x,
            y.view(np.int32) if y.dtype == np.float32 else y, err_msg=k)
    assert scans == [1, 1]                   # the zero-retrace contract
    for eng in (e0, e1):
        fc = eng.fault_counters()
        assert int(fc["fault_total"][st0[2].sid]) > 0   # c0 really faulted
        assert eng.counters()["nonfinite"] > 0


# --------------------------------------------------------------------------
# quarantine purge + redelivery refusal
# --------------------------------------------------------------------------

def test_quarantine_purges_queue_to_dlq_and_redeliver_refuses():
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    mid = reg.create_composite(t, "mid", ["v"], [s0], {"v": "s0.v"})
    end = reg.create_composite(t, "end", ["v"], [mid], {"v": "mid.v + 1"})
    eng = create_engine(reg)
    eng.post(s0, [7.0], ts=50)
    eng.round()                              # mid emitted; queued for end
    assert bool(np.asarray(eng.state.q_valid).any())
    eng.quarantine(mid)
    assert eng.counters()["dropped_poisoned"] == 1
    letters = eng.dead_letters(clear=False)
    assert [(l.sid, l.reason, l.ts, float(l.vals[0]), l.tenant)
            for l in letters] == [(mid.sid, "poisoned", 50, 7.0, 0)]
    # redelivery refuses the still-quarantined sid: the letter *stays*
    # (original reason preserved) and the refusal is counted
    assert eng.redeliver() == 0
    assert eng.counters()["redeliver_rejected"] == 1
    kept = eng.dead_letters(clear=False)
    assert [(l.sid, l.reason) for l in kept] == [(mid.sid, "poisoned")]
    # lifting the quarantine lets the SU back through end to end
    eng.unquarantine(mid)
    assert eng.redeliver() == 1
    assert eng.dead_letters(clear=False) == []
    eng.round()
    assert float(eng.value_of(end)[0]) == 8.0


def test_quarantine_gates_ingest():
    eng, src, comp = _poison_rig()
    eng.quarantine(src)
    eng.post(src, [3.0], ts=1)
    eng.round()
    assert eng.counters()["dropped_poisoned"] == 1
    assert [l.reason for l in eng.dead_letters()] == ["poisoned"]
    assert eng.counters()["processed"] == 0


# --------------------------------------------------------------------------
# checkpoint checksums + newest-valid fallback
# --------------------------------------------------------------------------

def _ckpt_rig(tmp_path, n_ckpts=3):
    eng, src, comp = _poison_rig(checkpoint_every=1)
    eng.checkpoint_to(str(tmp_path), keep=n_ckpts + 2)
    for i in range(n_ckpts):
        eng.post(src, [float(i)], ts=i + 1)
        eng.superstep(1)
    eng._ckpt.wait()
    return eng, sorted(ckpt.all_steps(str(tmp_path)))


@pytest.mark.parametrize("mode", ["truncate", "bitflip", "manifest"])
def test_corrupt_newest_falls_back_to_older(tmp_path, mode):
    eng, steps = _ckpt_rig(tmp_path)
    assert len(steps) >= 2
    path = str(tmp_path)
    if mode == "bitflip":
        # flip the last data byte of a leaf by hand (deterministic: an
        # rng-placed flip may land in npy header padding and stay benign)
        import os
        d = os.path.join(path, f"step_{steps[-1]:08d}")
        leaf = sorted(f for f in os.listdir(d) if f.endswith(".npy"))[0]
        with open(os.path.join(d, leaf), "r+b") as f:
            f.seek(-1, 2)
            b = f.read(1)
            f.seek(-1, 2)
            f.write(bytes([b[0] ^ 0x80]))
    else:
        assert C.corrupt_checkpoint(path, np.random.default_rng(0),
                                    mode=mode) is not None
    assert not ckpt.verify(path, steps[-1])
    assert ckpt.verify(path, steps[-2])
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.load(path, steps[-1])           # explicit step: hard error
    got, _, _ = ckpt.load_latest_valid(path)
    assert got == steps[-2]                  # newest *valid* wins
    eng2 = restore_engine(path)
    assert eng2 is not None and eng2._steps_done == steps[-2]


def test_all_checkpoints_corrupt_restores_none(tmp_path):
    _, steps = _ckpt_rig(tmp_path)
    rng = np.random.default_rng(1)
    for s in steps:
        C.corrupt_checkpoint(str(tmp_path), rng, mode="manifest", step=s)
    assert ckpt.load_latest_valid(str(tmp_path)) == (None, None, None)
    assert restore_engine(str(tmp_path)) is None


def test_checksum_catches_leaf_swap(tmp_path):
    """Same shape/dtype, different bytes: only the CRC can catch it."""
    eng, steps = _ckpt_rig(tmp_path, n_ckpts=1)
    import os
    d = os.path.join(str(tmp_path), f"step_{steps[-1]:08d}")
    leaves = sorted(f for f in os.listdir(d) if f.endswith(".npy"))
    victim = next(os.path.join(d, f) for f in leaves
                  if np.load(os.path.join(d, f)).size)
    arr = np.load(victim)
    raw = bytearray(arr.tobytes())
    raw[0] ^= 0xFF
    np.save(victim, np.frombuffer(bytes(raw), arr.dtype).reshape(arr.shape))
    assert not ckpt.verify(str(tmp_path), steps[-1])


# --------------------------------------------------------------------------
# the supervisor
# --------------------------------------------------------------------------

def _sup_rig(tmp_path, n_steps, poison_steps=(), ck_every=2, threshold=2):
    cfg = _cfg(checkpoint_every=ck_every, fault_window=8,
               fault_threshold=threshold)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    src = reg.create_stream(t, "src", ["v"])
    comp = reg.create_composite(t, "comp", ["v"], [src],
                                {"v": "src.v * 2.0"})
    eng = create_engine(reg)
    sid = src.sid

    def feed(e, step):
        bad = step in poison_steps
        e.post(sid, [np.nan if bad else float(step)], ts=step + 1)
    return eng, comp, feed


def test_supervisor_recovers_bit_identical(tmp_path):
    n_steps, kill_at = 10, 6

    def chaos(e, step):
        if step == kill_at:
            raise C.ShardKill("injected")

    eng, comp, feed = _sup_rig(tmp_path / "a", n_steps, poison_steps=(2,))
    report = supervised_run(eng, str(tmp_path / "a"), n_steps,
                            feed=feed, chaos=chaos, K=1)
    assert report.recovered and len(report.incidents) == 1
    inc = report.incidents[0]
    assert inc.kind == "crash" and "ShardKill" in inc.detail
    assert 0 < inc.restored_step <= kill_at
    assert inc.replayed_steps == kill_at - inc.restored_step + 1
    assert report.engine._steps_done == n_steps
    assert report.mttr_s > 0
    # bit-identical to an undisturbed twin driving the same feed
    twin, _, tfeed = _sup_rig(tmp_path / "b", n_steps, poison_steps=(2,),
                              ck_every=0)
    for step in range(n_steps):
        tfeed(twin, step)
        twin.superstep(1)
    a, _ = report.engine.snapshot()
    b, _ = twin.snapshot()
    assert set(a) == set(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        eq = np.array_equal(x, y, equal_nan=True) \
            if np.issubdtype(x.dtype, np.floating) else np.array_equal(x, y)
        assert eq, k
    # structured incident log round-trips
    import json
    log = json.loads(report.to_json())
    assert log["incidents"][0]["step"] == kill_at


def test_supervisor_skips_torn_checkpoint(tmp_path):
    n_steps, kill_at = 10, 7
    rng = np.random.default_rng(3)

    def chaos(e, step):
        if step == kill_at:
            e._ckpt.wait()
            assert C.corrupt_checkpoint(str(tmp_path), rng,
                                        mode="truncate") is not None
            raise C.ShardKill("kill with torn newest")

    eng, comp, feed = _sup_rig(tmp_path, n_steps)
    torn = None

    report = supervised_run(eng, str(tmp_path), n_steps,
                            feed=feed, chaos=chaos, K=1)
    assert report.recovered
    inc = report.incidents[0]
    # the newest (torn) checkpoint was at steps_done 6; recovery must have
    # fallen back past it
    assert inc.restored_step < 6
    assert report.engine._steps_done == n_steps
    del torn


def test_supervisor_blame_and_escalation(tmp_path):
    # breaker disarmed (threshold=0): faults count but never auto-trip,
    # so only the supervisor's escalation can quarantine the offender
    n_steps = 12
    kills = {4, 8}

    def chaos(e, step):
        if step in kills:
            raise C.ShardKill("injected")

    eng, comp, feed = _sup_rig(tmp_path, n_steps,
                               poison_steps=(1, 2, 3), threshold=0)
    sup = Supervisor(eng, str(tmp_path), feed=feed, chaos=chaos, K=1,
                     blame_faults=1, escalate_after=2)
    report = sup.run(n_steps)
    assert report.recovered and len(report.incidents) == 2
    assert report.incidents[0].blamed == [comp.sid]
    assert report.incidents[0].escalated == []
    assert report.incidents[1].blamed == [comp.sid]
    assert report.incidents[1].escalated == [comp.sid]   # 2nd strike
    assert sup.engine.is_quarantined(comp.sid)


def test_supervisor_gives_up_without_any_checkpoint(tmp_path):
    def chaos(e, step):
        if step == 0:                        # dies before any save lands
            raise C.ShardKill("early death")

    eng, comp, feed = _sup_rig(tmp_path, 4, ck_every=50)
    sup = Supervisor(eng, str(tmp_path), feed=feed, chaos=chaos, K=1,
                     max_retries=2, backoff0_s=0.01)
    with pytest.raises(RuntimeError, match="recovery failed"):
        sup.run(4)
    assert sup.last_report.recovered is False
    assert sup.incidents[-1].retries == 2


def test_supervisor_stall_watchdog(tmp_path):
    import time as _t
    slow = {3}

    def chaos(e, step):
        if step in slow:
            _t.sleep(0.2)

    eng, comp, feed = _sup_rig(tmp_path, 6)
    sup = Supervisor(eng, str(tmp_path), feed=feed, chaos=chaos, K=1,
                     step_budget_s=30.0)     # generous while compiling
    sup.step(0)
    sup.step_budget_s = 0.15                 # now arm a tight budget
    incs = [sup.step(s) for s in range(1, 6)]
    stalls = [i for i in incs if i is not None and i.kind == "stall"]
    assert len(stalls) >= 1 and stalls[0].step == 3
    assert sup.engine._steps_done == 6


# --------------------------------------------------------------------------
# seeded chaos soak (slow tier)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_soak_200_supersteps(tmp_path):
    """200 supervised supersteps under a seeded ChaosMonkey schedule
    (poison bursts + two kills, one with a torn newest checkpoint): the
    run must recover every time, never retrace, keep the breaker's books
    conserved, and finish bit-identical to an undisturbed twin."""
    n_steps, seed = 200, 17
    monkey = C.ChaosMonkey(seed, n_steps, p_poison=0.15, p_storm=0.0,
                           kill_steps=(70, 150), tear_steps=(150,))
    poison = sorted({e.step for e in monkey.events if e.kind == "poison"})
    kills = {e.step for e in monkey.events if e.kind == "kill"}
    tears = {e.step for e in monkey.events if e.kind == "tear"}

    def rig(path, ck):
        eng, comp, feed = _sup_rig(path, n_steps, poison_steps=poison,
                                   ck_every=ck, threshold=3)
        return eng, comp, feed

    def chaos(e, step):
        if step in tears:
            e._ckpt.wait()
            C.corrupt_checkpoint(str(tmp_path / "a"), monkey.rng,
                                 mode="truncate")
        if step in kills:
            raise C.ShardKill(f"soak kill @{step}")

    eng, comp, feed = rig(tmp_path / "a", 8)
    scan0 = compile_count(eng._superstep_fn(1))
    report = supervised_run(eng, str(tmp_path / "a"), n_steps,
                            feed=feed, chaos=chaos, K=1,
                            escalate_after=10**9)
    assert report.recovered and len(report.incidents) == 2
    assert report.engine._steps_done == n_steps
    # no retrace: the first engine and each restored one compile once
    assert compile_count(report.engine._superstep_fns[1]) - scan0 \
        == 1 + len(report.incidents)
    fc = report.engine.fault_counters()
    assert int(fc["fault_total"].sum()) == len(poison)
    assert bool(fc["quarantined"][comp.sid])          # breaker did trip

    twin, _, tfeed = rig(tmp_path / "b", 0)
    for step in range(n_steps):
        tfeed(twin, step)
        twin.superstep(1)
    a, _ = report.engine.snapshot()
    b, _ = twin.snapshot()
    assert set(a) == set(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        eq = np.array_equal(x, y, equal_nan=True) \
            if np.issubdtype(x.dtype, np.floating) else np.array_equal(x, y)
        assert eq, k


# --------------------------------------------------------------------------
# serving bridge: quarantined sources drop at the pump
# --------------------------------------------------------------------------

def test_bridge_drops_quarantined_deferred():
    from types import SimpleNamespace
    from repro.serving.bridge import ModelBackedStreams
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    src = reg.create_stream(t, "src", ["v"])
    model = reg.create_composite(t, "m", ["v"], [src], {"v": "src.v"},
                                 model_backed=True)
    resp = reg.create_stream(t, "m.response", ["score"])
    eng = create_engine(reg)
    batcher = SimpleNamespace(cfg=SimpleNamespace(vocab=64),
                              submit=lambda req: None, run_ticks=lambda n: [],
                              queue=[], live=[])
    br = ModelBackedStreams(eng, batcher, watermark=0)
    br.route(model, resp)
    # force a deferral: backlog the tenant over the watermark
    br._occ = np.array([10] * cfg.n_tenants)
    assert br._submit(model.sid, np.array([1.0], np.float32), 0) == 0
    assert len(br.deferred) == 1
    # quarantine the source before the deferred emission is released
    eng.quarantine(model)
    assert br.release_deferred() == 0
    assert br.deferred == [] and br.dropped_quarantined == 1
    # a healthy source still flows once the backlog clears (a new pump
    # burst re-reads both the occupancy and quarantine snapshots)
    eng.unquarantine(model)
    br._refresh_backpressure()
    assert br._submit(model.sid, np.array([1.0], np.float32), 0) == 1
