"""IoT workload suite & latency plane (ISSUE 9).

Covers the ingest-timestamp plane end to end:

* latency accounting properties — ingest stamps are conserved through
  enqueue/pop/re-enqueue/exchange and retained-emission replay, latency
  is non-negative and FIFO-monotone (hypothesis when installed, pinned
  cases always);
* fused vs staged differential — bit-identical latency records and SLO
  reports at 1 and 2 shards, K in {1, 3};
* the QoS regression — fair-share weights must improve an adversarially
  starved light tenant's p99 latency, and live SLO-knob churn must never
  retrace;
* the superstep round-attribution pin — sink records of the second
  superstep carry superstep-global emission rounds, not scan-local ones;
* SLOTracker unit semantics and the autoscaler's SLO scale-up signal.
"""
from types import SimpleNamespace

import numpy as np
import pytest

try:        # the hypothesis property test skips without it; the pinned
    from hypothesis import given, settings, strategies as st  # cases still run
except ImportError:
    def given(*a, **k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

    def settings(*a, **k):
        return lambda f: f

    class st:                                # placeholder strategy namespace
        @staticmethod
        def lists(*a, **k):
            return None

        @staticmethod
        def integers(*a, **k):
            return None

from repro.core import EngineConfig, Registry, create_engine
from repro.core.slo import SLOTracker, weights_from_slo
from repro.launch.compiles import compile_count
from repro.workloads import TraceConfig, build_suite
from repro.workloads.runner import sink_records


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _chain(n_shards: int = 1, superstep: int = 1, retention: int = 0,
           fused: bool = True):
    """a -> b -> c depth chain; returns (eng, tenant, [a, b, c])."""
    cfg = EngineConfig(n_streams=16, n_tenants=4, channels=2, max_in=2,
                       max_out=2, batch=8, queue=64, prog_len=16,
                       n_temps=8, sink_buffer=16, n_shards=n_shards,
                       superstep=superstep, retention_slots=retention,
                       dlq_slots=8, exchange_slots=0,
                       fused_round=fused).validate()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    b = reg.create_composite(t, "b", ["v"], [a], {"v": "in0.v + 1"})
    c = reg.create_composite(t, "c", ["v"], [b], {"v": "in0.v * 2"})
    return create_engine(reg), t, [a, b, c]


def _depth_of(streams):
    """Hops from ingest to each *composite*'s emission (phase-0 ingest
    dispatches a source SU straight to its subscribers, so the first
    composite emits in the ingest round itself — depth 0; sources never
    emit sink records of their own)."""
    return {s.sid: d for d, s in enumerate(streams[1:])}


def _collect_rounds(eng, schedule, streams):
    """Drive one round per schedule entry (n posts to the source), return
    (records dict, its stamps recorded at post time)."""
    a = streams[0]
    posted_its = []
    recs = []
    for r, n_posts in enumerate(schedule):
        for j in range(n_posts):
            posted_its.append(eng._rounds_done)
            eng.post(a, [float(r * 10 + j)], ts=r * 10 + j + 1)
        sink = eng.round()
        recs.append(eng.latency_records(sink))
    # settle: everything in flight reaches its sink
    for _ in range(len(streams) + 2):
        recs.append(eng.latency_records(eng.round()))
    out = {k: np.concatenate([r[k] for r in recs]) for k in recs[0]}
    return out, posted_its


def _check_accounting(recs, posted_its, depth, exact: bool):
    """The conservation properties every drive mode must satisfy.
    ``exact`` (at most one post per round): latency equals pipeline
    depth; otherwise same-round SUs to one stream serialize (one SU per
    stream per round), so depth is only a lower bound."""
    assert np.all(recs["latency"] >= 0)
    assert np.all(recs["latency"] == recs["round"] - recs["its"])
    # stamps are conserved: every observed its was assigned at a post
    assert set(recs["its"].tolist()) <= set(posted_its)
    for sid in np.unique(recs["sid"]):
        mine = np.nonzero(recs["sid"] == sid)[0]
        if exact:
            assert np.all(recs["latency"][mine] == depth[int(sid)])
        else:
            assert np.all(recs["latency"][mine] >= depth[int(sid)])
        # FIFO: emission order preserves ingest order per stream
        order = mine[np.argsort(recs["round"][mine], kind="stable")]
        assert np.all(np.diff(recs["its"][order]) >= 0)
    # completeness: each post surfaces once per pipeline stage
    for d in set(depth.values()):
        stage = [s for s, dd in depth.items() if dd == d]
        n = int(np.isin(recs["sid"], stage).sum())
        assert n == len(posted_its)


# --------------------------------------------------------------------------
# satellite 1: latency-accounting properties
# --------------------------------------------------------------------------

PINNED_SCHEDULES = [
    [1],
    [2, 0, 1],
    [0, 3, 0, 0, 2, 1],
    [1, 1, 1, 1, 1, 1, 1, 1],
]


@pytest.mark.parametrize("schedule", PINNED_SCHEDULES)
@pytest.mark.parametrize("n_shards", [1, 2])
def test_latency_accounting_pinned(schedule, n_shards):
    eng, _, streams = _chain(n_shards=n_shards)
    recs, posted = _collect_rounds(eng, schedule, streams)
    _check_accounting(recs, posted, _depth_of(streams),
                      exact=max(schedule) <= 1)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=4), min_size=1,
                max_size=10))
def test_latency_accounting_property(schedule):
    eng, _, streams = _chain()
    recs, posted = _collect_rounds(eng, schedule, streams)
    _check_accounting(recs, posted, _depth_of(streams),
                      exact=max(schedule) <= 1)


@pytest.mark.parametrize("K", [2, 3])
def test_latency_accounting_superstep(K):
    """Same conservation laws when rounds run K-fused in one scan."""
    eng, _, streams = _chain(superstep=K)
    a = streams[0]
    posted = []
    recs = []
    for step in range(4):
        for j in range(1 + step % 2):
            posted.append(eng._rounds_done)
            eng.post(a, [float(step + j)], ts=step * 10 + j + 1)
        recs.append(eng.latency_records(eng.superstep(K)))
    for _ in range(3):
        recs.append(eng.latency_records(eng.superstep(K)))
    out = {k: np.concatenate([r[k] for r in recs]) for k in recs[0]}
    _check_accounting(out, posted, _depth_of(streams), exact=False)


def test_replay_keeps_original_stamp():
    """Retained emissions replayed to a late joiner keep their original
    ingest stamp: the replayed SU's latency clock spans the detour."""
    eng, t, (a, b, c) = _chain(retention=4)
    stamps = []
    for r in range(3):
        stamps.append(eng._rounds_done)
        eng.post(a, [float(r)], ts=r + 1)
        eng.round()
    for _ in range(5):                       # let history age
        eng.round()
    d = eng.admit_composite(t, "d", ["v"], [b], {"v": "in0.v + 100"})
    assert d is not None
    late_round = eng._rounds_done
    assert eng.admit_subscription(d, a, replay=True)
    recs = []
    for _ in range(4):
        recs.append(eng.latency_records(eng.round()))
    out = {k: np.concatenate([r[k] for r in recs]) for k in recs[0]}
    assert eng.counters()["replayed"] == len(stamps)
    mine = out["sid"] == d.sid
    # the replayed SUs pop together and collapse to one emission whose
    # clock starts at the *oldest* original stamp (conservative
    # accounting) — NOT at the admission round, which would read 0
    assert mine.sum() == 1
    assert out["its"][mine].tolist() == [stamps[0]]
    assert np.all(out["round"][mine] >= late_round)
    assert np.all(out["latency"][mine] >= late_round - stamps[0])


# --------------------------------------------------------------------------
# satellite 4 (pin): superstep-global round attribution at K > 1
# --------------------------------------------------------------------------

def test_superstep_round_attribution_is_global():
    """Records of the *second* superstep must carry engine-global
    emission rounds (base + scan-local round), not the scan-local tags —
    scan-local attribution makes every post-first-superstep latency
    negative."""
    eng, _, (a, b, c) = _chain(superstep=3)
    eng.post(a, [1.0], ts=1)
    r1 = eng.latency_records(eng.superstep(3))
    by_sid = dict(zip(r1["sid"].tolist(), r1["round"].tolist()))
    assert by_sid == {b.sid: 0, c.sid: 1}
    eng.post(a, [2.0], ts=2)                 # stamped its = 3
    r2 = eng.latency_records(eng.superstep(3))
    by_sid = dict(zip(r2["sid"].tolist(), r2["round"].tolist()))
    assert by_sid == {b.sid: 3, c.sid: 4}
    assert np.all(r2["its"] == 3)
    assert sorted(r2["latency"].tolist()) == [0, 1]


# --------------------------------------------------------------------------
# satellite 2: fused vs staged latency differential
# --------------------------------------------------------------------------

def _drive_suite(fused: bool, n_shards: int, K: int):
    suite = build_suite(
        4, kinds=("etl", "stats"), n_shards=n_shards, fused_round=fused,
        trace=TraceConfig(n_devices=4, rounds=8, seed=11),
        cfg_overrides={"superstep": K})
    eng = suite.engine
    per_step = []
    for k, dev, vals in suite.trace.steps():
        for d, v in zip(dev, vals):
            eng.post(suite.flows[d].source, [float(v)], ts=k + 1)
        recs = eng.latency_records(eng.superstep(K))
        per_step.append(recs)
        suite.slo.observe(sink_records(recs, suite.sink_sids))
    for _ in range(3):
        recs = eng.latency_records(eng.superstep(K))
        per_step.append(recs)
        suite.slo.observe(sink_records(recs, suite.sink_sids))
    return suite, per_step


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("K", [1, 3])
def test_fused_staged_latency_bitwise(n_shards, K):
    sa, ra = _drive_suite(True, n_shards, K)
    sb, rb = _drive_suite(False, n_shards, K)
    if n_shards == 1:
        assert sa.engine._path == "fused"    # the differential is real
        assert sb.engine._path == "staged"
    for x, y in zip(ra, rb):
        for key in x:
            np.testing.assert_array_equal(x[key], y[key], err_msg=key)
    np.testing.assert_array_equal(sa.slo.hist, sb.slo.hist)
    np.testing.assert_array_equal(sa.slo.violations, sb.slo.violations)
    assert sa.slo.slo_report() == sb.slo.slo_report()


# --------------------------------------------------------------------------
# satellite 3: QoS weights must improve the starved tenant's p99 latency
# --------------------------------------------------------------------------

def _adversarial(qos_on: bool):
    """A heavy amplification chain next to one light 2-hop pipeline.

    The WFQ pop only arbitrates the *emission queue*: posted SUs are
    ingest-dispatched straight through their depth-0 composite, and a
    popped emission fans out to every subscriber within the pop round —
    so ``batch`` caps popped *emissions*, not executions.  Contention
    therefore needs a tenant whose per-round emission count exceeds the
    pop budget at depth >= 1: heavy's one post explodes into 8 mid-stage
    emissions (hA -> hM0..hM7 -> hS_j) against a batch of 4, burying the
    queue, while light's single lA emission (lA -> lB) competes with it.
    FIFO (weights off) makes every light emission wait behind the whole
    heavy backlog; weighted-fair pop (light=8, heavy=1) tags light's
    head-of-line emission 0 and serves it within a round."""
    cfg = EngineConfig(n_streams=32, n_tenants=4, channels=2, max_in=2,
                       max_out=8, batch=4, queue=512, prog_len=16,
                       n_temps=8, sink_buffer=32, exchange_slots=0).validate()
    reg = Registry.with_capacity(cfg)
    heavy = reg.create_tenant("heavy", quota_streams=10 ** 9)
    light = reg.create_tenant("light", quota_streams=10 ** 9)
    h_src = reg.create_stream(heavy, "h", ["v"])
    h_amp = reg.create_composite(heavy, "hA", ["v"], [h_src],
                                 {"v": "in0.v"})
    for j in range(8):
        mid = reg.create_composite(heavy, f"hM{j}", ["v"], [h_amp],
                                   {"v": f"in0.v + {j}"})
        reg.create_composite(heavy, f"hS{j}", ["v"], [mid],
                             {"v": "in0.v * 2.0"})
    l_src = reg.create_stream(light, "l", ["v"])
    l_mid = reg.create_composite(light, "lA", ["v"], [l_src],
                                 {"v": "in0.v"})
    l_sink = reg.create_composite(light, "lB", ["v"], [l_mid],
                                  {"v": "in0.v + 1"})
    eng = create_engine(reg)
    if qos_on:
        eng.set_weight(light, 8)
        eng.set_weight(heavy, 1)
    slo = SLOTracker(4, slo={light.tid: 2})
    for r in range(20):
        eng.post(h_src, [float(r)], ts=10 * r + 1)  # heavy floods first
        eng.post(l_src, [float(r)], ts=10 * r + 2)
        sink = eng.round()
        slo.observe(sink_records(eng.latency_records(sink), [l_sink.sid]))
    for _ in range(120):                        # drain the whole backlog
        sink = eng.round()
        slo.observe(sink_records(eng.latency_records(sink), [l_sink.sid]))
        if not bool(eng.state.q_valid.any()):
            break
    return eng, heavy, light, slo


def test_qos_weights_improve_light_p99():
    _, _, light_off, slo_off = _adversarial(qos_on=False)
    eng, heavy, light, slo_on = _adversarial(qos_on=True)
    p99_off = slo_off.percentile(99, light_off)
    p99_on = slo_on.percentile(99, light)
    assert slo_on.count(light) > 0
    assert p99_on < p99_off, (p99_on, p99_off)
    # and the shaped tenant actually meets its 2-round SLO
    assert slo_on.pressure()[light.tid] < slo_off.pressure()[light_off.tid]

    # zero-retrace churn: close the SLO -> weights loop live, every round
    cache0 = compile_count(eng._step)
    for r in range(6):
        slo_on.set_slo(light, 2 + r % 2)
        w = weights_from_slo(slo_on, base=1, boost=8)
        for tid in (heavy.tid, light.tid):
            eng.set_weight(tid, int(w[tid]))
        slo_on.observe(eng.latency_records(eng.round()))
    assert compile_count(eng._step) - cache0 == 0


# --------------------------------------------------------------------------
# SLOTracker unit semantics + autoscaler hookup
# --------------------------------------------------------------------------

def _recs(tenants, lats):
    n = len(tenants)
    return {"sid": np.zeros(n, np.int32),
            "tenant": np.asarray(tenants, np.int32),
            "its": np.zeros(n, np.int32),
            "round": np.asarray(lats, np.int32),
            "latency": np.asarray(lats, np.int32)}


def test_slo_tracker_percentiles_exact():
    tr = SLOTracker(2, slo={0: 5})
    tr.observe(_recs([0] * 100, list(range(100))))
    assert tr.count(0) == 100
    assert tr.percentile(50, 0) == 49        # nearest-rank on 0..99
    assert tr.percentile(95, 0) == 94
    assert tr.percentile(99, 0) == 98
    assert tr.percentile(100, 0) == 99
    assert int(tr.violations[0]) == 94       # latencies 6..99 violate 5
    assert tr.percentile(50, 1) == -1        # silent tenant: no data
    rep = tr.slo_report()
    assert rep["tenants"][0]["violation_rate"] == pytest.approx(0.94)
    assert 1 not in rep["tenants"]
    # unresolved tenants (-1) and overflow bucketing are absorbed safely
    tr.observe(_recs([-1, 0], [3, 10 ** 6]))
    assert tr.count() == 101
    assert tr.percentile(100, 0) == tr.n_buckets * tr.bucket_width - 1


def test_weights_from_slo_boosts_violators():
    tr = SLOTracker(3, slo={0: 1, 1: 1})
    tr.observe(_recs([0] * 10, [5] * 10))     # 100% violating
    tr.observe(_recs([1] * 10, [0] * 10))     # compliant
    w = weights_from_slo(tr, base=1, boost=8)
    assert w[0] == 9 and w[1] == 1 and w[2] == 1


def test_autoscaler_scales_up_on_slo_pressure():
    """A violation-rate spike must trigger an immediate scale-up with
    reason "slo", like fresh drops do — decision logic pinned against an
    engine stub so no device mesh is needed."""
    from repro.launch.autoscale import Autoscaler
    resized = []
    eng = SimpleNamespace(
        cfg=SimpleNamespace(n_shards=1, queue=64),
        counters=lambda: {"dropped_overflow": 0},
        tenant_backlog=lambda: np.zeros(2),
        resize=lambda n, mesh=None: resized.append(n))
    tr = SLOTracker(2, slo={0: 1})
    sc = Autoscaler(eng, max_shards=4, patience=99, cooldown=0, slo=tr,
                    slo_up=0.05)
    tr.observe(_recs([0] * 8, [0] * 8))      # healthy window
    assert sc.observe() is None and resized == []
    tr.observe(_recs([0] * 8, [9] * 8))      # 100% violations
    ev = sc.observe()
    assert ev is not None and ev.reason == "slo" and resized == [2]
    eng.cfg.n_shards = 2
    tr.observe(_recs([0] * 8, [0] * 8))      # healthy again: no flap
    assert sc.observe() is None and resized == [2]
