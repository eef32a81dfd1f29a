"""Tenant QoS plane (ISSUE 4): the weighted-fair ``_pop`` must match a
brute-force weighted-fair/deficit oracle pop-for-pop (hypothesis property
+ deterministic cases), guarantee starvation-freedom (every weighted
tenant with queued SUs is served within ``ceil(active_tenants / batch)``
rounds), enforce per-tenant ingest token buckets (over-quota SUs shed
into ``dropped_quota``, never the queue), surface per-tenant backpressure
to the host/bridge/batcher, and — like every plane in this repo — never
retrace across live ``set_weight`` / ``set_quota`` edits at 1 and 2
shards."""
import math
from types import SimpleNamespace

import numpy as np
import pytest

try:        # the hypothesis-based tests skip without it; the deterministic
    from hypothesis import given, settings, strategies as st  # ones still run
except ImportError:
    def given(*a, **k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

    def settings(*a, **k):
        return lambda f: f

    class st:                                # placeholder strategy namespace
        @staticmethod
        def composite(f):
            return lambda *a, **k: None

import jax
import jax.numpy as jnp
from jax import monitoring

from repro.core import EngineConfig, Registry, create_engine, init_state
from repro.core.engine import FAIR_SCALE, _enqueue, _pop
from repro.launch.compiles import compile_count

N_DEV = len(jax.devices())

_TRACES = []
monitoring.register_event_duration_secs_listener(
    lambda name, dur, **kw: _TRACES.append(name)
    if name.startswith("/jax/core/compile") else None)


def _require(n_shards):
    if N_DEV < n_shards:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV}")


# --------------------------------------------------------------------------
# the brute-force oracle: per-round recomputed weighted-fair order
# --------------------------------------------------------------------------

def oracle_drain(items, batch, prio_by_sid, tenant_by_sid, weight):
    """Brute-force weighted-fair drain (pure python, O(n^2)): each round,
    rank every remaining item within its tenant by (priority, seq), tag
    rank k of a weight-w tenant with k*FAIR_SCALE//w (0 when w == 0), pop
    the ``batch`` smallest (priority, tag, seq).  Returns the per-round
    lists of popped seqs."""
    remaining = list(items)                  # (sid, ts, seq)
    rounds = []
    while remaining:
        ranks = {}
        tagged = []
        for it in sorted(remaining,
                         key=lambda x: (prio_by_sid[x[0]], x[2])):
            t = tenant_by_sid[it[0]]
            k = ranks.get(t, 0)
            ranks[t] = k + 1
            w = weight[t]
            tag = (k * FAIR_SCALE) // w if w > 0 else 0
            tagged.append((prio_by_sid[it[0]], tag, it[2], it))
        tagged.sort(key=lambda x: x[:3])
        take = [x[3] for x in tagged[:batch]]
        rounds.append([it[2] for it in take])
        for it in take:
            remaining.remove(it)
    return rounds


def _drain_pop(cfg, items, batch, prio, tenant, weight):
    """Drain the real ``_pop`` on a queue holding ``items`` (sid, ts, seq
    implicit by enqueue order); returns per-round popped seq lists."""
    state = init_state(cfg)
    sid = jnp.asarray([i[0] for i in items], jnp.int32)
    vals = jnp.zeros((len(items), cfg.channels), jnp.float32)
    ts = jnp.asarray([i[1] for i in items], jnp.int32)
    state, dropped = _enqueue(state, sid, vals, ts, jnp.ones(len(items), bool))
    assert int(dropped) == 0
    prio_j = jnp.asarray(prio, jnp.int32)
    ten_j = jnp.asarray(tenant, jnp.int32)
    w_j = jnp.asarray(weight, jnp.int32)
    rounds = []
    while bool(state.q_valid.any()):
        state, (p_sid, _, p_ts, _, p_valid) = _pop(state, prio_j, batch,
                                                   ten_j, w_j)
        seqs = []
        for s, t, v in zip(np.asarray(p_sid), np.asarray(p_ts),
                           np.asarray(p_valid)):
            if v:
                # recover the seq from (sid, ts): items are unique pairs
                seqs.append(next(q for (qs, qt, q) in
                                 [(i[0], i[1], i[2]) for i in items]
                                 if qs == s and qt == t))
        rounds.append(seqs)
    return rounds


def _mk_items(sids, base_ts=100):
    """(sid, unique-ts, seq) with seq = enqueue order (matching _enqueue,
    which numbers from state.seq+1 upward; only relative order matters)."""
    return [(s, base_ts + j, j + 1) for j, s in enumerate(sids)]


def _cfg(**kw):
    base = dict(n_streams=16, n_tenants=4, batch=8, queue=64, max_in=4,
                max_out=4, prog_len=24, n_temps=12)
    base.update(kw)
    return EngineConfig(**base)


# --------------------------------------------------------------------------
# differential: _pop == oracle, deterministic and property-based
# --------------------------------------------------------------------------

def _check_vs_oracle(sids, tenant_of_sid, weight, prio, batch):
    cfg = _cfg(n_streams=max(sids) + 1 if sids else 2, queue=64,
               n_tenants=len(weight), batch=batch)
    items = _mk_items(sids)
    got = _drain_pop(cfg, items, batch, prio, tenant_of_sid, weight)
    want = oracle_drain(items, batch, prio, tenant_of_sid, weight)
    assert got == want


def test_pop_matches_oracle_deterministic():
    """Two backlogged tenants at weights 3:1 interleave 3-to-1; a third
    zero-weight tenant is unshaped (tag 0 on every SU)."""
    tenant = [0, 1, 2, 0]          # sid -> tenant
    weight = [3, 1, 0]
    prio = [0, 0, 0, 0]
    sids = [0, 1, 0, 1, 0, 1, 0, 1, 3, 3]
    _check_vs_oracle(sids, tenant, weight, prio, batch=2)


def test_pop_composes_with_priority():
    """Per-sid priority stays the primary key: a lower-priority class is
    exhausted before any higher one, and fairness applies within."""
    tenant = [0, 1, 0, 1]
    weight = [1, 1]
    prio = [0, 0, 5, 5]            # sids 2/3 served strictly later
    sids = [2, 3, 0, 1, 2, 3, 0, 1]
    _check_vs_oracle(sids, tenant, weight, prio, batch=3)


def test_pop_all_zero_weights_is_fifo():
    """The all-zero weight table must reproduce the pre-QoS (priority,
    seq) pop bit-exactly — including against _pop run *without* QoS args."""
    cfg = _cfg(batch=4)
    items = _mk_items([5, 1, 5, 2, 9, 1, 7, 3])
    prio = np.zeros(cfg.n_streams, np.int32)
    tenant = (np.arange(cfg.n_streams) % cfg.n_tenants).tolist()
    weight = [0] * cfg.n_tenants
    got = _drain_pop(cfg, items, 4, prio, tenant, weight)
    assert [s for r in got for s in r] == [1, 2, 3, 4, 5, 6, 7, 8]
    # and identical to the legacy signature
    state = init_state(cfg)
    sid = jnp.asarray([i[0] for i in items], jnp.int32)
    state, _ = _enqueue(state, sid, jnp.zeros((8, cfg.channels)),
                        jnp.asarray([i[1] for i in items], jnp.int32),
                        jnp.ones(8, bool))
    _, (legacy_sid, _, _, _, _) = _pop(state, jnp.asarray(prio), 4)
    assert np.asarray(legacy_sid).tolist() == [5, 1, 5, 2]


@st.composite
def _pop_cases(draw):
    n_tenants = draw(st.integers(1, 4))
    n_sids = draw(st.integers(1, 8))
    tenant = [draw(st.integers(0, n_tenants - 1)) for _ in range(n_sids)]
    weight = [draw(st.integers(0, 5)) for _ in range(n_tenants)]
    prio = [draw(st.integers(0, 3)) for _ in range(n_sids)]
    n_items = draw(st.integers(1, 24))
    sids = [draw(st.integers(0, n_sids - 1)) for _ in range(n_items)]
    batch = draw(st.integers(1, 6))
    return sids, tenant, weight, prio, batch


@settings(max_examples=60, deadline=None)
@given(_pop_cases())
def test_pop_matches_oracle_property(case):
    sids, tenant, weight, prio, batch = case
    _check_vs_oracle(sids, tenant, weight, prio, batch)


# --------------------------------------------------------------------------
# starvation-freedom: bounded service interval for every weighted tenant
# --------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(_pop_cases())
def test_starvation_freedom_bound(case):
    """At equal priority, a weighted tenant's head SU always carries
    virtual tag 0 — so whenever a tenant with queued SUs is passed over,
    every pop slot that round went to a strictly *older* SU.  Since the
    older backlog only shrinks, any tenant's wait is bounded by
    ceil(older_backlog / batch) rounds: no weight assignment can starve a
    tenant out of the pop.  Also checks work conservation: the drain
    takes exactly ceil(n / batch) rounds."""
    sids, tenant, weight, prio, batch = case
    weight = [max(w, 1) for w in weight]     # all tenants weighted
    prio = [0] * len(prio)                   # single priority class
    cfg = _cfg(n_streams=max(sids) + 1, queue=64,
               n_tenants=len(weight), batch=batch)
    items = _mk_items(sids)
    rounds = _drain_pop(cfg, items, batch, prio, tenant, weight)
    assert len(rounds) == math.ceil(len(items) / batch)   # work-conserving
    seq_tenant = {i[2]: tenant[i[0]] for i in items}
    pending = {i[2] for i in items}
    for served in rounds:
        passed_over = {seq_tenant[q] for q in pending} \
            - {seq_tenant[q] for q in served}
        for t in passed_over:
            head = min(q for q in pending if seq_tenant[q] == t)
            assert all(q < head for q in served), \
                f"tenant {t} (head seq {head}) starved by younger SUs"
        pending -= set(served)
    assert not pending


def test_weighted_share_proportional():
    """Two fully backlogged tenants at weights 3:1 split the pops ~3:1
    (within one batch of the ideal split at every prefix)."""
    tenant = [0, 1]
    weight = [3, 1]
    prio = [0, 0]
    sids = [0, 1] * 16                       # 16 SUs each, interleaved
    cfg = _cfg(n_streams=2, queue=64, n_tenants=2, batch=4)
    items = _mk_items(sids)
    rounds = _drain_pop(cfg, items, 4, prio, tenant, weight)
    seq_tenant = {i[2]: tenant[i[0]] for i in items}
    got0 = 0
    seen = 0
    for served in rounds:
        got0 += sum(1 for q in served if seq_tenant[q] == 0)
        seen += len(served)
        if seen <= 16:      # both tenants still backlogged
            ideal = seen * 3 / 4
            assert abs(got0 - ideal) <= 4, (seen, got0, ideal)


# --------------------------------------------------------------------------
# ingest quotas: token buckets, shed accounting
# --------------------------------------------------------------------------

def _quota_engine(n_shards=1):
    cfg = _cfg(n_shards=n_shards)
    reg = Registry.with_capacity(cfg)
    t0 = reg.create_tenant("shaped")
    t1 = reg.create_tenant("free")
    srcs = [reg.create_stream(t0, f"s{i}", ["v"]) for i in range(4)]
    other = reg.create_stream(t1, "o", ["v"])
    eng = create_engine(reg)
    return eng, t0, t1, srcs, other


def test_quota_sheds_over_limit_and_counts():
    eng, t0, t1, srcs, other = _quota_engine()
    eng.set_quota(t0, 1)                     # 1 token/round, burst 1
    for s in srcs[:3]:                       # 3 same-tenant SUs, one round
        eng.post(s, [1.0], ts=1)
    eng.post(other, [1.0], ts=1)             # unlimited tenant untouched
    eng.round()
    c = eng.counters()
    assert c["dropped_quota"] == 2
    tc = eng.tenant_counters()
    assert tc["dropped_quota"].tolist()[:2] == [2, 0]
    assert c["ingested"] == 4                # arrivals still counted
    # exactly one shaped SU (batch order: srcs[0]) + the free tenant's got in
    assert eng.ts_of(srcs[0]) == 1
    assert eng.ts_of(srcs[1]) < 0 and eng.ts_of(srcs[2]) < 0
    assert eng.ts_of(other) == 1


def test_quota_bucket_accrues_to_burst():
    eng, t0, _, srcs, _ = _quota_engine()
    eng.set_quota(t0, 1, burst=3)
    for _ in range(5):                       # idle rounds refill to burst=3
        eng.round()
    assert int(np.asarray(eng.state.tokens).reshape(-1)[t0.tid]) == 3
    for s in srcs:                           # 4 arrivals, 3 tokens
        eng.post(s, [2.0], ts=5)
    eng.round()
    assert eng.counters()["dropped_quota"] == 1
    # tightening the quota clamps the bucket immediately
    for _ in range(5):
        eng.round()
    eng.set_quota(t0, 1, burst=2)
    assert int(np.asarray(eng.state.tokens).reshape(-1)[t0.tid]) <= 2
    eng.set_quota(t0, 0)                     # 0 = unlimited again
    for s in srcs:
        eng.post(s, [3.0], ts=20)
    before = eng.counters()["dropped_quota"]
    eng.round()
    assert eng.counters()["dropped_quota"] == before
    # a huge quota is clipped, so the refill can't overflow int32 into
    # shedding everything (regression: tokens + quota wrap-around)
    eng.set_quota(t0, 2 ** 31 - 1, burst=2 ** 31 - 1)
    for r in range(3):
        for s in srcs:
            eng.post(s, [4.0 + r], ts=30 + r)
        eng.round()
    assert eng.counters()["dropped_quota"] == before


def test_quota_sheds_do_not_crowd_queue_or_store():
    """Shed SUs vanish in phase 0: no last-value store, no queue slot, no
    downstream processing."""
    eng, t0, _, srcs, _ = _quota_engine()
    c = eng.registry.create_composite(
        eng.registry.tenants[1], "c", ["v"], [srcs[1]], {"v": "in0.v * 2"})
    eng.rewire()
    eng.set_quota(t0, 1)
    eng.post(srcs[0], [1.0], ts=1)           # takes the only token
    eng.post(srcs[1], [7.0], ts=1)           # shed
    eng.drain()
    assert eng.ts_of(srcs[1]) < 0
    assert eng.value_of(c)[0] == 0.0         # subscriber never fired
    assert eng.counters()["dropped_quota"] == 1
    assert eng.tenant_backlog(t0) == 0


# --------------------------------------------------------------------------
# backpressure: occupancy surfacing + bridge/batcher watermark hook
# --------------------------------------------------------------------------

def test_tenant_backlog_tracks_queue_occupancy():
    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    b = reg.create_composite(t, "b", ["v"], [a], {"v": "in0.v + 1"})
    reg.create_composite(t, "c", ["v"], [b], {"v": "in0.v + 1"})
    eng = create_engine(reg)
    eng.post(a, [1.0], ts=1)
    eng.round()                              # b's emission re-enqueued
    assert eng.tenant_backlog(t) == 1
    assert eng.tenant_counters()["queued"][t.tid] == 1
    eng.drain()
    assert eng.tenant_backlog(t) == 0
    occ = eng.tenant_backlog()               # full per-tenant array
    assert occ.shape == (cfg.n_tenants,) and occ.sum() == 0


def test_bridge_watermark_defers_and_releases():
    from repro.serving.bridge import ModelBackedStreams

    cfg = _cfg()
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    chain = reg.create_composite(t, "x", ["v"], [a], {"v": "in0.v + 1"})
    reg.create_composite(t, "y", ["v"], [chain], {"v": "in0.v + 1"})
    eng = create_engine(reg)
    eng.drain()

    submitted = []
    batcher = SimpleNamespace(cfg=SimpleNamespace(vocab=64),
                              submit=submitted.append, queue=[], live=[],
                              throttle=None)
    mbs = ModelBackedStreams(eng, batcher, watermark=0)
    assert batcher.throttle is not None      # batcher half of the hook
    out = mbs.admit_route(t, "scorer", [a], prompt_len=4)
    assert out is not None
    model, _resp = out

    eng.post(a, [1.0], ts=1)
    eng.round()                              # chain emission queued: occ > 0
    assert eng.tenant_backlog(t) > 0
    assert mbs._submit(model.sid, np.ones(4, np.float32)) == 0
    assert len(mbs.deferred) == 1 and not submitted   # pump slowed
    assert batcher.throttle(SimpleNamespace(tenant=t.tid))

    eng.drain()                              # backlog clears the watermark
    assert eng.tenant_backlog(t) == 0
    assert mbs.release_deferred() == 1
    assert len(submitted) == 1 and not mbs.deferred
    assert submitted[0].tenant == t.tid


def test_batcher_throttle_passes_over_blocked_requests():
    from collections import deque
    from repro.serving.batcher import ContinuousBatcher, Request

    b = object.__new__(ContinuousBatcher)    # no model: queue logic only
    b.queue = deque([Request(rid=0, prompt=[1], tenant=0),
                     Request(rid=1, prompt=[1], tenant=1),
                     Request(rid=2, prompt=[1], tenant=0)])
    b.throttle = lambda req: req.tenant == 0
    got = b._next_admittable()
    assert got.rid == 1                      # skipped the throttled head
    assert b._next_admittable() is None      # the rest all wait
    assert [r.rid for r in b.queue] == [0, 2]    # order preserved
    b.throttle = None
    assert b._next_admittable().rid == 0     # hook cleared -> plain FIFO


def test_sharded_exchange_overflow_charged_to_emitting_tenant():
    """Cross-shard exchange drops must be attributed to the *emitting*
    stream's tenant (whose sids this shard owns and can resolve) — never
    through the remote target sid, which would read an unrelated row of
    the local tenant slice."""
    _require(2)
    cfg = EngineConfig(n_streams=16, n_tenants=4, batch=16, queue=64,
                       max_in=2, max_out=4, n_shards=2, exchange_slots=1)
    reg = Registry.with_capacity(cfg)
    prod = reg.create_tenant("producer")      # tid 0, emits cross-shard
    cons = reg.create_tenant("consumer")      # tid 1, owns the targets
    a = reg.create_stream(prod, "a", ["v"])   # sid 0 -> shard 0
    for i in range(7):
        reg.create_stream(prod, f"pad{i}", ["v"])   # fill shard 0
    subs = [reg.create_composite(cons, f"c{i}", ["v"], [a],
                                 {"v": "a.v + 1"}) for i in range(3)]
    eng = create_engine(reg)
    assert all(eng.plan.sid_to_shard[s.sid] == 1 for s in subs)
    eng.post(a, [1.0], ts=1)
    eng.drain()
    c = eng.counters()
    assert c["dropped_overflow"] == 2         # 3 targets, 1 exchange slot
    tc = eng.tenant_counters()["dropped_overflow"]
    assert tc[prod.tid] == 2 and tc[cons.tid] == 0


# --------------------------------------------------------------------------
# zero-retrace contract across live weight/quota edits, 1 and 2 shards
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2])
def test_qos_edits_zero_retrace(n_shards):
    _require(n_shards)
    cfg = _cfg(n_shards=n_shards)
    reg = Registry.with_capacity(cfg)
    t0 = reg.create_tenant("t0")
    t1 = reg.create_tenant("t1")
    srcs = [reg.create_stream(t0, f"s{i}", ["v"]) for i in range(2)]
    srcs += [reg.create_stream(t1, f"u{i}", ["v"]) for i in range(2)]
    comps = [reg.create_composite(t1, f"c{i}", ["v"], [s],
                                  {"v": "in0.v + 1"})
             for i, s in enumerate(srcs)]
    eng = create_engine(reg)
    K = 3
    step0 = compile_count(eng._step)
    scan0 = compile_count(eng._superstep_fn(K))

    # warm: the round, the superstep scan, and both QoS ops
    eng.post(srcs[0], [1.0], 1)
    eng.round()
    eng.superstep(K)
    eng.set_weight(t0, 1)
    eng.set_quota(t0, 1, 1)
    jax.block_until_ready(eng.state.timestamps)
    cache_step = compile_count(eng._step) - step0
    cache_scan = compile_count(eng._superstep_fns[K]) - scan0
    n_traces = len(_TRACES)

    ts = 10
    for r in range(6):                       # live knob churn under traffic
        eng.set_weight(t0, 1 + r)
        eng.set_weight(t1, 7 - r)
        eng.set_quota(t0, 1 + r % 2, 2)
        eng.set_quota(t1, 0)
        for s in srcs:
            eng.post(s, [float(r)], ts)
        eng.round() if r % 2 else eng.superstep(K)
        ts += K + 1
    jax.block_until_ready(eng.state.timestamps)

    assert compile_count(eng._step) - step0 == cache_step == 1
    assert compile_count(eng._superstep_fns[K]) - scan0 == cache_scan == 1
    assert len(_TRACES) == n_traces, \
        f"QoS knob edits recompiled: {_TRACES[n_traces:]}"
    # and the knobs actually took: t0 is shaped, t1 unlimited
    assert int(np.asarray(eng.tables.weight).reshape(-1, cfg.n_tenants)
               [0, t0.tid]) == 6
    del comps
