"""Superstep execution plane: a K-round compiled scan must be
bit-identical to K sequential ``round()`` calls — stats, sink batches and
the final EngineState (queue included) — at every K and shard count, with
admission churn applied only at superstep boundaries, and without ever
retracing as the queue depth changes between supersteps."""
import dataclasses

import numpy as np
import pytest

import jax
from jax import monitoring

from repro.core import EngineConfig, Registry, create_engine
from repro.core.engine import StreamEngine
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


def _cfg(**kw):
    base = dict(n_streams=16, n_tenants=4, batch=8, queue=64, max_in=4,
                max_out=4, prog_len=24, n_temps=12)
    base.update(kw)
    return EngineConfig(**base)


def _build(cfg):
    """Deterministic multi-hop topology with fan-out, fan-in and a filter;
    identical between calls so two engines start bit-identical."""
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
    comps.append(reg.create_composite(t, "c4", ["v"], [comps[3], srcs[3]],
                                      {"v": "in0.v + in1.v"}))
    return reg, srcs, comps, create_engine(reg)


def _post_schedule(eng, srcs, waves=3):
    """Posts with waves, same-ts ties and same-stream bursts (bursts longer
    than small K exercise the ring's persistent overflow queue)."""
    ts = 1
    for w in range(waves):
        for i, s in enumerate(srcs):
            eng.post(s, [float(10 * w + i)], ts)
        eng.post(srcs[0], [float(w)], ts + 1)     # same-ts tie material
        eng.post(srcs[1], [float(w)], ts + 1)
        for b in range(5):                        # same-stream burst
            eng.post(srcs[2], [float(100 * w + b)], ts + 2 + b)
        ts += 8


def _state_leaves(eng):
    st = eng.state
    leaves = {f: np.asarray(getattr(st, f))
              for f in ("values", "timestamps", "q_sid", "q_vals", "q_ts",
                        "q_seq", "q_valid", "seq", "tenant_emitted")}
    leaves.update({f"stat.{k}": np.asarray(v) for k, v in st.stats.items()})
    return leaves


def _assert_engines_equal(eA, eB):
    a, b = _state_leaves(eA), _state_leaves(eB)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"state leaf {k}")


def _assert_sinks_equal(sinksA, sinksB):
    assert len(sinksA) == len(sinksB)
    for k, (sa, sb) in enumerate(zip(sinksA, sinksB)):
        for f, x, y in zip(sa._fields, sa, sb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"sink round {k} field {f}")


# --------------------------------------------------------------------------
# the differential suite: superstep(K) == K sequential rounds
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
@pytest.mark.parametrize("K", [1, 3, 64])
def test_superstep_bit_identical_to_rounds(n_shards, K):
    _require(n_shards)
    cfg = _cfg(n_shards=n_shards)
    _, srcsA, _, engA = _build(cfg)
    _, srcsB, _, engB = _build(cfg)
    _post_schedule(engA, srcsA)
    _post_schedule(engB, srcsB)

    sinksA = [engA.round() for _ in range(K)]
    sinksB = engB.spool_sinks(engB.superstep(K))

    _assert_engines_equal(engA, engB)
    _assert_sinks_equal(sinksA, sinksB)
    assert engA.counters() == engB.counters()
    # leftovers of the burst stayed pending on both (identically)
    assert [(e[0], e[2]) for e in engA._pending] == \
        [(e[0], e[2]) for e in engB._pending]
    for ea, eb in zip(engA._pending, engB._pending):
        np.testing.assert_array_equal(ea[1], eb[1])


@pytest.mark.parametrize("n_shards", [1, 2])
def test_superstep_churn_at_boundaries_bit_identical(n_shards):
    """Admission churn lands only between supersteps; the churned
    superstep engine stays bit-identical to the churned per-round engine
    and the compiled scan never retraces."""
    _require(n_shards)
    K = 3
    cfg = _cfg(n_shards=n_shards)
    _, srcsA, compsA, engA = _build(cfg)
    _, srcsB, compsB, engB = _build(cfg)

    # trace the scan + warm every admission op before counting
    scan0 = compile_count(engB._superstep_fn(K))
    for eng, srcs in ((engA, srcsA), (engB, srcsB)):
        eng.post(srcs[0], [1.0], 1)
    _ = [engA.round() for _ in range(K)]
    engB.superstep(K)
    for eng, srcs in ((engA, srcsA), (engB, srcsB)):
        t = eng.registry.tenants[0]
        warm = eng.admit_composite(t, "warm", ["v"], [srcs[0]],
                                   {"v": "in0.v"})
        eng.revoke_stream(warm)
    cacheA = compile_count(engB._superstep_fns[K]) - scan0
    jax.block_until_ready(engB.tables.active)
    n_traces = len(_TRACES)

    grown = {engA: [], engB: []}
    for phase in range(3):
        for eng, srcs in ((engA, srcsA), (engB, srcsB)):
            t = eng.registry.tenants[0]
            s = eng.admit_composite(t, f"live{phase}", ["v"],
                                    [srcs[phase]], {"v": f"in0.v + {phase}"})
            assert s is not None
            grown[eng].append(s)
            if phase == 1:       # revoke the first live admission mid-run
                eng.revoke_stream(grown[eng].pop(0))
        ts0 = 100 + 10 * phase
        for eng, srcs in ((engA, srcsA), (engB, srcsB)):
            for i, s in enumerate(srcs):
                eng.post(s, [float(phase + i)], ts0)
        _ = [engA.round() for _ in range(K)]
        engB.superstep(K)

    jax.block_until_ready(engB.state.timestamps)
    assert compile_count(engB._superstep_fns[K]) - scan0 == cacheA == 1
    assert len(_TRACES) == n_traces, \
        f"superstep churn recompiled: {_TRACES[n_traces:]}"
    _assert_engines_equal(engA, engB)
    assert engA.counters() == engB.counters()


def test_superstep_zero_retrace_across_queue_depth():
    """The trace-counter acceptance check: wildly different backlogs (and
    therefore queue depths and ring occupancies) between supersteps must
    reuse the one compiled scan."""
    cfg = _cfg()
    _, srcs, _, eng = _build(cfg)
    K = 4
    scan0 = compile_count(eng._superstep_fn(K))
    eng.post(srcs[0], [1.0], 1)
    eng.superstep(K)                      # first trace
    jax.block_until_ready(eng.state.timestamps)
    n_traces = len(_TRACES)
    ts = 10
    for depth in (0, 1, 7, 40):           # incl. > K*batch backlog
        for j in range(depth):
            eng.post(srcs[j % len(srcs)], [float(j)], ts)
            eng.post(srcs[2], [float(j)], ts + 1)   # same-stream burst
        eng.superstep(K)
        ts += 5
    jax.block_until_ready(eng.state.timestamps)
    assert compile_count(eng._superstep_fns[K]) - scan0 == 1
    assert len(_TRACES) == n_traces, \
        f"queue depth retraced: {_TRACES[n_traces:]}"


# --------------------------------------------------------------------------
# sink-spool overflow accounting
# --------------------------------------------------------------------------

def test_sink_spool_overflow_counted_not_silent():
    """Emissions beyond sink_spool_slots land in dropped_spool — the spool
    keeps the first entries intact and the books always balance."""
    cfg = EngineConfig(n_streams=16, batch=8, queue=64, max_in=1, max_out=6,
                       sink_spool_slots=2)
    reg = Registry(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    subs = [reg.create_composite(t, f"c{i}", ["v"], [a], {"v": "a.v + 1"})
            for i in range(6)]
    eng = create_engine(reg)
    eng.post(a, [1.0], ts=1)
    spool = eng.superstep(2)              # round 0 ingests, round 1 emits x6
    c = eng.counters()
    assert c["emitted"] == 6
    assert c["dropped_spool"] == 4        # 6 emissions, 2 spool rows
    assert int(spool.fill) == 2
    # the retained prefix is exact, never truncated to garbage
    assert np.asarray(spool.sid)[:2].tolist() == [subs[0].sid, subs[1].sid]
    assert np.asarray(spool.ts)[:2].tolist() == [1, 1]
    np.testing.assert_array_equal(np.asarray(spool.vals)[:2, 0], [2.0, 2.0])


def test_sink_spool_overflow_sharded():
    _require(2)
    cfg = EngineConfig(n_streams=16, batch=8, queue=64, max_in=1, max_out=6,
                       n_shards=2, sink_spool_slots=2)
    reg = Registry(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    for i in range(7):
        reg.create_stream(t, f"p{i}", ["v"])
    subs = [reg.create_composite(t, f"c{i}", ["v"], [a], {"v": "a.v + 1"})
            for i in range(6)]           # all on shard 1 (block partition)
    eng = create_engine(reg)
    eng.post(a, [1.0], ts=1)
    spool = eng.superstep(2)
    c = eng.counters()
    assert c["emitted"] == 6
    assert c["dropped_spool"] == 4       # shard 1 spilled 4 of its 6
    assert int(np.asarray(spool.fill).sum()) == 2
    del subs


def test_spool_default_capacity_never_overflows():
    cfg = _cfg()                          # sink_spool_slots=0 -> K*sink_buffer
    _, srcs, _, eng = _build(cfg)
    for w in range(4):
        for s in srcs:
            eng.post(s, [float(w)], w + 1)
    eng.superstep(8)
    assert eng.counters()["dropped_spool"] == 0


# --------------------------------------------------------------------------
# drain / serving integration
# --------------------------------------------------------------------------

def test_drain_rides_supersteps_equivalent():
    """cfg.superstep > 1 routes drain() through the superstep plane; the
    final state and the merged emission log match the per-round drain."""
    cfgA, cfgB = _cfg(), _cfg(superstep=4)
    _, srcsA, _, engA = _build(cfgA)
    _, srcsB, _, engB = _build(cfgB)
    _post_schedule(engA, srcsA)
    _post_schedule(engB, srcsB)
    sinksA = engA.drain()
    sinksB = engB.drain()
    _assert_engines_equal(engA, engB)

    def emissions(sinks):
        out = []
        for s in sinks:
            v = np.asarray(s.valid)
            out += list(zip(np.asarray(s.sid)[v].tolist(),
                            np.asarray(s.ts)[v].tolist(),
                            np.asarray(s.vals)[v][:, 0].tolist()))
        return out

    assert emissions(sinksA) == emissions(sinksB)


def test_bridge_pump_spool_matches_pump():
    """The serving bridge consumes a superstep spool identically to the
    equivalent per-round sink batches."""
    from repro.serving.bridge import ModelBackedStreams
    from types import SimpleNamespace

    def build():
        cfg = _cfg()
        reg = Registry.with_capacity(cfg)
        t = reg.create_tenant("t")
        a = reg.create_stream(t, "a", ["v"])
        m = reg.create_composite(t, "m", ["req"], [a], {"req": "a.v"},
                                 model_backed=True)
        eng = create_engine(reg)
        submitted = []
        batcher = SimpleNamespace(cfg=SimpleNamespace(vocab=64),
                                  submit=lambda req: submitted.append(req),
                                  run_ticks=lambda n: [],
                                  queue=[], live=[])
        mbs = ModelBackedStreams(eng, batcher)
        mbs.route(m, a)
        return eng, a, mbs, submitted

    engA, aA, mbsA, subA = build()
    engB, aB, mbsB, subB = build()
    for eng, a in ((engA, aA), (engB, aB)):
        eng.post(a, [1.0], 1)
        eng.post(a, [2.0], 2)
    nA = sum(mbsA.pump(s, ts=5) for s in mbsA.engine.spool_sinks(
        engA.superstep(4)))
    nB = mbsB.pump_spool(engB.superstep(4), ts=5)
    assert nA == nB == len(subA) == len(subB) > 0
    assert [r.prompt for r in subA] == [r.prompt for r in subB]

    # serve() drives one superstep end to end on a fresh post
    engB.post(aB, [3.0], 9)
    assert mbsB.serve(ts=10, K=4) == 1


def test_bridge_pump_spool_order_matches_per_round_sharded():
    """On a sharded engine, pump_spool must submit round-major (like the
    per-round pump path), not shard-major — request ids feed completion
    timestamps, so the order is semantics, not cosmetics."""
    _require(2)
    from repro.serving.bridge import ModelBackedStreams
    from types import SimpleNamespace

    def build():
        cfg = EngineConfig(n_streams=16, batch=8, queue=64, max_in=2,
                           max_out=4, n_shards=2)
        reg = Registry(cfg)
        t = reg.create_tenant("t")
        a = reg.create_stream(t, "a", ["v"])                 # sid 0, shard 0
        ma = reg.create_composite(t, "ma", ["q"], [a], {"q": "a.v"},
                                  model_backed=True)         # sid 1, shard 0
        md = reg.create_composite(t, "md", ["q"], [ma], {"q": "ma.q"},
                                  model_backed=True)         # sid 2, shard 0
        for i in range(5):
            reg.create_stream(t, f"p{i}", ["v"])             # sids 3..7
        mb = reg.create_composite(t, "mb", ["q"], [a], {"q": "a.v"},
                                  model_backed=True)         # sid 8, shard 1
        mc = reg.create_composite(t, "mc", ["q"], [mb], {"q": "mb.q"},
                                  model_backed=True)         # sid 9, shard 1
        eng = create_engine(reg)
        batcher = SimpleNamespace(cfg=SimpleNamespace(vocab=64),
                                  submit=lambda req: None,
                                  run_ticks=lambda n: [],
                                  queue=[], live=[])
        mbs = ModelBackedStreams(eng, batcher)
        for m in (ma, mb, mc, md):
            mbs.route(m, a)
        return eng, a, mbs

    def order(mbs):     # source sids in rid (submission) order
        return [mbs.inflight[rid].source_sid for rid in sorted(mbs.inflight)]

    engA, aA, mbsA = build()
    engB, aB, mbsB = build()
    engA.post(aA, [1.0], 1)
    engB.post(aB, [1.0], 1)
    # per-round path: round-major, shard-concatenated sinks
    for sink in engA.spool_sinks(engA.superstep(4)):
        mbsA.pump(sink, ts=5)
    mbsB.pump_spool(engB.superstep(4), ts=5)
    assert order(mbsA) == order(mbsB)
    # both shards emitted in two different rounds -> the orders differ
    # between round-major and shard-major; round-major interleaves shards
    assert order(mbsA) == [1, 8, 2, 9]
