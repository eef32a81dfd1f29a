"""Dynamic admission plane: live churn on a running engine must (a) never
recompile the round — asserted with a jax.monitoring trace counter and the
jitted step's cache size — and (b) end bit-identical to a freshly built
static registry with the same final topology, single-device and sharded.
Plus the edge cases: full-table rejection (counted), revoke-then-readmit
of a recycled sid, swap_program equivalence, rebalance migration."""
import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

import jax
from jax import monitoring

from repro.core import EngineConfig, Registry, StreamEngine, create_engine
from repro.core.engine import INT_MIN
from repro.launch.compiles import compile_count

N_DEV = len(jax.devices())

# every (re)trace of any jitted function appends an event here
_TRACES = []
monitoring.register_event_duration_secs_listener(
    lambda name, dur, **kw: _TRACES.append(name)
    if name.startswith("/jax/core/compile") else None)


def _require(n_shards):
    if N_DEV < n_shards:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV}")


# --------------------------------------------------------------------------
# a deterministic topology, buildable statically or admitted live
# --------------------------------------------------------------------------

def _grow(make_stream, make_comp):
    """Create the same multi-hop topology through either path: static
    ``Registry.create_*`` or live ``StreamEngine.admit_*`` callbacks.
    Creation order fixes the sid sequence, so both paths produce the same
    sid layout."""
    srcs = [make_stream(f"s{i}") for i in range(4)]
    comps = [
        make_comp("c0", [srcs[0]], "in0.v + 1", None),
        make_comp("c1", [srcs[0], srcs[1]], "in0.v + in1.v * 2", None),
        make_comp("c2", [srcs[2]], "in0.v * 3", "out.v < 1e6"),
    ]
    comps.append(make_comp("c3", [comps[0], comps[1]], "in0.v - in1.v", None))
    comps.append(make_comp("c4", [comps[3], srcs[3]], "in0.v + in1.v", None))
    return srcs, comps


def _schedule(srcs, waves=3):
    sched, ts = [], 1
    for w in range(waves):
        wave = [(srcs[i], [float(10 * w + i)], ts) for i in range(len(srcs))]
        wave.append((srcs[0], [float(w)], ts + 1))   # same-ts tie material
        wave.append((srcs[1], [float(w)], ts + 1))
        sched.append(wave)
        ts += 3
    return sched


def _run(eng, sched):
    for wave in sched:
        for stream, vals, ts in wave:
            eng.post(stream, vals, ts)
        eng.drain(max_rounds=64)


def _cfg(**kw):
    base = dict(n_streams=16, n_tenants=4, batch=32, queue=128, max_in=4,
                max_out=4, prog_len=24, n_temps=12)
    base.update(kw)
    return EngineConfig(**base)


def _global_state(eng):
    if hasattr(eng, "plan"):
        plan = eng.plan
        v = np.asarray(eng.state.values).reshape(
            plan.n_shards * plan.n_local, -1)[plan.sid_to_flat]
        t = np.asarray(eng.state.timestamps).reshape(-1)[plan.sid_to_flat]
        return v, t
    return np.asarray(eng.state.values), np.asarray(eng.state.timestamps)


# --------------------------------------------------------------------------
# zero recompilation + bit-exact equivalence with a static build
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2])
def test_live_churn_zero_retrace_bit_identical(n_shards):
    """The acceptance criterion: admitting streams + subscriptions on a
    running (already-traced) engine triggers zero recompilations, and the
    churned engine is bit-identical to a fresh static registry with the
    same final topology."""
    _require(n_shards)
    cfg = _cfg(n_shards=n_shards)

    # live-churned engine: two seed sources, everything else admitted live
    regA = Registry.with_capacity(cfg)
    tA = regA.create_tenant("t")
    seed0 = regA.create_stream(tA, "s0", ["v"])
    seed1 = regA.create_stream(tA, "s1", ["v"])
    engA = create_engine(regA)
    step0 = compile_count(engA._step)
    engA.drain(max_rounds=2)           # trace the round before any churn

    # warm every admission op once (their own one-time compiles), then
    # count traces across the real churn + processing phase
    warm = engA.admit_composite(tA, "warm", ["v"], [seed0], {"v": "in0.v"})
    engA.admit_subscription(warm, seed1)
    engA.revoke_subscription(warm, seed1)
    engA.swap_program(warm, {"v": "in0.v + 1"})
    engA.revoke_stream(warm)
    cache0 = compile_count(engA._step) - step0
    jax.block_until_ready(engA.tables.active)
    n_traces = len(_TRACES)

    mkA = lambda n: engA.admit_stream(tA, n, ["v"])
    mcA = lambda n, ins, tr, pf: engA.admit_composite(
        tA, n, ["v"], ins, {"v": tr}, post_filter=pf)
    seed_srcs = [seed0, seed1]
    srcsA, compsA = _grow(
        lambda n: seed_srcs.pop(0) if seed_srcs else mkA(n), mcA)
    engA.admit_subscription(compsA[2], srcsA[3])      # live rewire
    _run(engA, _schedule(srcsA))
    _run(engA, _schedule(srcsA, waves=2))
    jax.block_until_ready(engA.state.timestamps)

    assert compile_count(engA._step) - step0 == cache0 == 1
    assert len(_TRACES) == n_traces, \
        f"churn recompiled: {_TRACES[n_traces:]}"

    # static reference: same creation order, same final topology
    regB = Registry.with_capacity(cfg)
    tB = regB.create_tenant("t")
    mkB = lambda n: regB.create_stream(tB, n, ["v"])
    mcB = lambda n, ins, tr, pf: regB.create_composite(
        tB, n, ["v"], ins, {"v": tr}, post_filter=pf)
    srcsB, compsB = _grow(mkB, mcB)
    regB.subscribe(compsB[2], srcsB[3])
    engB = create_engine(regB)
    engB.drain(max_rounds=2)
    _run(engB, _schedule(srcsB))
    _run(engB, _schedule(srcsB, waves=2))

    vA, tsA = _global_state(engA)
    vB, tsB = _global_state(engB)
    np.testing.assert_array_equal(tsA, tsB)
    np.testing.assert_array_equal(vA, vB)             # bit-identical
    cA, cB = engA.counters(), engB.counters()
    assert cA == cB


# --------------------------------------------------------------------------
# edge cases
# --------------------------------------------------------------------------

def test_admit_full_table_rejected_counted():
    cfg = _cfg(n_streams=4, max_in=2)
    reg = Registry(cfg)                     # no spare capacity on purpose
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    streams = [reg.create_stream(t, f"p{i}", ["v"]) for i in range(3)]
    eng = create_engine(reg)

    assert eng.admit_stream(t, "overflow", ["v"]) is None
    assert eng.admission_rejected == 1
    assert eng.admit_composite(t, "oc", ["v"], [a], {"v": "in0.v"}) is None
    assert eng.admission_rejected == 2

    # in-degree exhaustion on a live composite is also counted
    cfg2 = _cfg(max_in=1)
    reg2 = Registry.with_capacity(cfg2, max_streams=8)
    t2 = reg2.create_tenant("t")
    x = reg2.create_stream(t2, "x", ["v"])
    y = reg2.create_stream(t2, "y", ["v"])
    c = reg2.create_composite(t2, "c", ["v"], [x], {"v": "in0.v"})
    eng2 = create_engine(reg2)
    assert not eng2.admit_subscription(c, y)
    assert eng2.admission_rejected == 1
    # the engine still runs after rejections
    eng2.post(x, [2.0], ts=1)
    eng2.drain()
    assert eng2.value_of(c)[0] == 2.0


def test_revoke_then_readmit_same_sid():
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    eng = create_engine(reg)
    c = eng.admit_composite(t, "c", ["v"], [a], {"v": "in0.v + 1"})
    eng.post(a, [7.0], ts=5)
    eng.drain()
    assert eng.value_of(c)[0] == 8.0 and eng.ts_of(a) == 5

    # two-hop chain so c's emission is *queued* when c is revoked
    d = eng.admit_composite(t, "d", ["v"], [c], {"v": "in0.v * 2"})
    eng.post(a, [9.0], ts=6)
    eng.round()                       # hop 1: c = 10, emission queued for d
    old_sid = c.sid
    eng.revoke_stream(c)              # purges the queued emission
    eng.drain()
    assert eng.counters()["dropped_revoked"] >= 1
    assert eng.ts_of(d) == INT_MIN            # d never fired

    # readmission recycles the lowest free sid and starts fresh
    c2 = eng.admit_stream(t, "c2", ["v"])
    assert c2.sid == old_sid
    assert eng.ts_of(c2) == INT_MIN and eng.value_of(c2)[0] == 0.0
    # a ts older than the revoked incarnation's emissions must be live
    eng.admit_subscription(d, c2)
    eng.post(c2, [1.0], ts=1)
    eng.drain()
    assert eng.value_of(c2)[0] == 1.0 and eng.ts_of(c2) == 1
    assert eng.value_of(d)[0] == 2.0          # rewired pipeline runs


def test_revoked_ingest_dropped_and_fanout_severed():
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    b = reg.create_stream(t, "b", ["v"])
    eng = create_engine(reg)
    c = eng.admit_composite(t, "c", ["v"], [a, b], {"v": "in0.v + in1.v"})
    eng.post(a, [1.0], ts=1)
    eng.post(b, [2.0], ts=1)
    eng.drain()
    assert eng.value_of(c)[0] == 3.0
    eng.revoke_stream(b)
    before = eng.counters()["dropped_revoked"]
    eng.post(b, [50.0], ts=2)                 # to a revoked stream
    eng.post(a, [4.0], ts=2)
    eng.drain()
    assert eng.counters()["dropped_revoked"] == before + 1
    assert eng.value_of(c)[0] == 4.0          # b's slot reads as absent


def test_validation_errors_propagate_and_roll_back():
    """Capacity exhaustion is a counted rejection; *validation* errors
    (bad user code, revoked inputs) raise and leave no half-admitted
    state behind."""
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    b = reg.create_stream(t, "b", ["v"])
    eng = create_engine(reg)
    c = eng.admit_composite(t, "c", ["v"], [a], {"v": "in0.v"})

    n_active = reg.n_active
    with pytest.raises(ValueError):          # missing transform channel
        eng.admit_composite(t, "bad", ["v"], [a], {})
    with pytest.raises(Exception):           # unknown identifier compiles late
        eng.admit_composite(t, "bad2", ["v"], [a], {"v": "nope.x"})
    assert reg.n_active == n_active          # rolled back, sid recycled
    assert eng.admission_rejected == 0       # not mistaken for capacity

    eng.revoke_stream(b)
    with pytest.raises(ValueError, match="revoked"):
        eng.registry.subscribe(c, b)         # host mirror refuses dead input
    with pytest.raises(ValueError, match="revoked"):
        eng.admit_composite(t, "d", ["v"], [b], {"v": "in0.v"})
    # engine still healthy after every rejection path
    eng.post(a, [6.0], ts=1)
    eng.drain()
    assert eng.value_of(c)[0] == 6.0


def test_swap_program_equivalence_vs_rebuilt_registry():
    """swap_program between rounds == a registry rebuilt with the new code,
    provided the pre-swap rounds never touched the swapped pipeline."""
    def build(transform_q):
        reg = Registry.with_capacity(_cfg())
        t = reg.create_tenant("t")
        p = reg.create_stream(t, "p", ["v"])
        q = reg.create_stream(t, "q", ["v"])
        pc = reg.create_composite(t, "pc", ["v"], [p], {"v": "in0.v + 1"})
        qc = reg.create_composite(t, "qc", ["v"], [q], {"v": transform_q})
        return reg, p, q, pc, qc

    regA, pA, qA, pcA, qcA = build("in0.v * 2")
    engA = create_engine(regA)
    engA.post(pA, [3.0], ts=1)                # wave 1: pipeline P only
    engA.drain()
    engA.swap_program(qcA, {"v": "in0.v * 100"})   # live mid-run swap
    engA.post(pA, [4.0], ts=2)
    engA.post(qA, [5.0], ts=2)
    engA.drain()

    regB, pB, qB, pcB, qcB = build("in0.v * 100")  # rebuilt with new code
    engB = create_engine(regB)
    engB.post(pB, [3.0], ts=1)
    engB.drain()
    engB.post(pB, [4.0], ts=2)
    engB.post(qB, [5.0], ts=2)
    engB.drain()

    vA, tsA = _global_state(engA)
    vB, tsB = _global_state(engB)
    np.testing.assert_array_equal(vA, vB)
    np.testing.assert_array_equal(tsA, tsB)
    assert engA.counters() == engB.counters()
    assert engA.value_of(qcA)[0] == 500.0


# --------------------------------------------------------------------------
# sharded plane
# --------------------------------------------------------------------------

def test_sharded_placement_and_occupancy():
    _require(2)
    cfg = _cfg(n_shards=2)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    eng = create_engine(reg)
    occ0 = eng._occupancy.copy()
    added = [eng.admit_stream(t, f"n{i}", ["v"]) for i in range(4)]
    # least-loaded routing keeps the spread at <= 1
    assert eng._occupancy.sum() == occ0.sum() + 4
    assert eng._occupancy.max() - eng._occupancy.min() <= 1
    for s in added:
        eng.revoke_stream(s)
    np.testing.assert_array_equal(eng._occupancy, occ0)
    del a


def test_sharded_rebalance_migrates_state():
    _require(2)
    cfg = _cfg(n_streams=12, n_shards=2, partition="tenant")
    reg = Registry.with_capacity(cfg)
    t0 = reg.create_tenant("even")            # tid 0 -> all on shard 0
    a = reg.create_stream(t0, "a", ["v"])
    eng = create_engine(reg)
    comps = [eng.admit_composite(t0, f"c{i}", ["v"], [a],
                                 {"v": f"in0.v + {i}"}) for i in range(4)]
    eng.post(a, [10.0], ts=1)
    eng.drain()
    assert eng._occupancy[0] - eng._occupancy[1] >= 4
    cache0 = compile_count(eng._step)

    moved = eng.rebalance()
    assert moved >= 2
    assert eng._occupancy.max() - eng._occupancy.min() <= 1
    # values travelled with their rows ...
    assert [float(eng.value_of(c)[0]) for c in comps] == [10, 11, 12, 13]
    # ... and the migrated pipeline keeps processing (now cross-shard)
    eng.post(a, [20.0], ts=2)
    eng.drain()
    assert [float(eng.value_of(c)[0]) for c in comps] == [20, 21, 22, 23]
    assert compile_count(eng._step) == cache0

    eng.post(a, [1.0], ts=3)                  # in-flight SUs block moves
    with pytest.raises(ValueError, match="flight|drain"):
        eng.rebalance()


def test_exchange_compaction_ignores_unrouted_items():
    """Regression: work items with no destination (empty fan-out slots,
    subscriber-less events) must not consume exchange-buffer ranks of the
    last shard.  Two events pop together — one with zero subscribers, one
    with two subscribers on shard 1 — under exchange_slots=2: both valid
    items must cross, dropped_overflow must stay 0."""
    _require(2)
    cfg = EngineConfig(n_streams=16, batch=16, queue=64, max_in=2, max_out=4,
                       n_shards=2, exchange_slots=2)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    p = reg.create_stream(t, "p", ["v"])       # sid 0, shard 0, no subs
    a = reg.create_stream(t, "a", ["v"])       # sid 1, shard 0
    for i in range(6):
        reg.create_stream(t, f"pad{i}", ["v"])  # sids 2..7 fill shard 0
    subs = [reg.create_composite(t, f"c{i}", ["v"], [a],
                                 {"v": "a.v + 1"}) for i in range(2)]
    eng = create_engine(reg)
    assert all(eng.plan.sid_to_shard[s.sid] == 1 for s in subs)
    eng.post(p, [1.0], ts=1)                   # pops first (lower seq)...
    eng.post(a, [2.0], ts=1)                   # ...its 4 dead items precede
    eng.drain()
    assert eng.counters()["dropped_overflow"] == 0
    assert all(eng.value_of(s)[0] == 3.0 for s in subs)


def test_sharded_revoked_fanout_drops_cleanly():
    """A queued emission whose subscriber was revoked mid-flight must drop
    into the counters, never fire into the vacated row."""
    _require(2)
    cfg = _cfg(n_shards=2)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    eng = create_engine(reg)
    c = eng.admit_composite(t, "c", ["v"], [a], {"v": "in0.v + 1"})
    eng.post(a, [1.0], ts=1)
    eng.round()                               # a stored + queued
    eng.revoke_stream(c)                      # c gone before dispatch
    eng.drain()
    v, ts = _global_state(eng)
    assert (ts[c.sid] == INT_MIN) and (v[c.sid] == 0).all()


# --------------------------------------------------------------------------
# registry mirror + windows + serving bridge
# --------------------------------------------------------------------------

def test_registry_capacity_and_recycling():
    cfg = _cfg(n_streams=4, max_in=2, max_out=2)
    reg = Registry.with_capacity(cfg, max_streams=8, max_subs=3)
    assert reg.cfg.n_streams == 8
    assert reg.cfg.max_in == 3 and reg.cfg.max_out == 3
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    b = reg.create_stream(t, "b", ["v"])
    c = reg.create_composite(t, "c", ["v"], [a, b], {"v": "a.v + in1.v"})
    reg.remove_stream(b)
    assert reg.streams[b.sid] is None
    # the edge is severed *in place* — the slot tombstones to -1 exactly
    # like the device in_table, so surviving slots keep their in<i>
    # register bindings and b-referencing expressions still recompile
    # (the tombstone remembers b's name/channels)
    assert c.inputs == [a.sid, -1]
    tab = reg.build_tables()
    assert tab.active.tolist() == [True, False, True] + [False] * 5
    assert tab.in_count[c.sid] == 1
    assert tab.in_table[c.sid].tolist() == [a.sid, -1, -1]
    d = reg.create_stream(t, "d", ["v"])
    assert d.sid == b.sid                     # lowest free sid recycled
    assert reg.n_active == 3
    # a new subscription reuses the tombstoned slot, as the device does
    reg.subscribe(c, d)
    assert c.inputs == [a.sid, d.sid]
    assert reg.build_tables().in_count[c.sid] == 2


def test_windows_reset_rows():
    import jax.numpy as jnp
    from repro.core.windows import aggregate, init_window_store, push
    from repro.core import admission

    st = init_window_store(4, 8, 1)
    sid = jnp.arange(4, dtype=jnp.int32)
    for i in range(3):
        st = push(st, sid, jnp.full((4, 1), float(i + 1)),
                  jnp.full((4,), i + 1, jnp.int32), jnp.ones((4,), bool))
    st = admission.reset_windows(st, jnp.int32(2))
    agg = aggregate(st, use_kernel=False)
    assert float(agg["count"][2, 0]) == 0 and float(agg["sum"][2, 0]) == 0
    assert float(agg["count"][1, 0]) == 3 and float(agg["sum"][1, 0]) == 6
    assert int(st.ptr[2]) == 0 and int(st.total[2]) == 0


def test_bridge_admit_route_mid_flight():
    from repro.serving.bridge import ModelBackedStreams

    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    eng = create_engine(reg)
    step0 = compile_count(eng._step)
    eng.post(a, [1.0], ts=1)
    eng.drain()                               # engine already running

    batcher = SimpleNamespace(cfg=SimpleNamespace(vocab=64),
                              submit=lambda req: None, queue=[], live=[])
    mbs = ModelBackedStreams(eng, batcher)
    out = mbs.admit_route(t, "scorer", [a], prompt_len=4)
    assert out is not None
    model, resp = out
    assert model.model_backed and model.sid in mbs.routes
    assert compile_count(eng._step) - step0 == 1  # no retrace from serving

    mbs.revoke_route(model)
    assert model.sid not in mbs.routes
    assert eng.registry.streams[model.sid] is None
    assert eng.registry.streams[resp.sid] is None

    # full table -> admit_route reports None and counts rejections
    small = Registry(_cfg(n_streams=2))
    ts2 = small.create_tenant("t")
    x = small.create_stream(ts2, "x", ["v"])
    y = small.create_stream(ts2, "y", ["v"])
    eng2 = create_engine(small)
    mbs2 = ModelBackedStreams(eng2, batcher)
    assert mbs2.admit_route(ts2, "m", [x]) is None
    assert eng2.admission_rejected >= 1
    del y
