"""Kernelized scheduler hot path (ISSUE 5): the packed selection pop —
pure-jnp ref and Pallas kernel alike — must be *bit-identical* to the
lexsort reference pop for every priority/weight/seq combination
(all-zero weight tables, zero-weight tenants, seq collisions among
stale slots, partially-valid queues, pathological INT_MAX/negative
priorities), at 1 and 2 shards, through rounds and supersteps; live
``set_weight``/``set_quota`` churn on the new default path must never
retrace; and the weighted-fair virtual tag must stay inside int32 at
the rank-clamp boundary (deep queue, weight 1)."""
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
from repro.core.engine import FAIR_SCALE, RANK_LIM, _enqueue, _pop
from repro.launch.compiles import compile_count
from repro.kernels.sched_pop.ops import sched_pop
from repro.kernels.sched_pop import ref as sched_ref

N_DEV = len(jax.devices())

_TRACES = []
monitoring.register_event_duration_secs_listener(
    lambda name, dur, **kw: _TRACES.append(name)
    if name.startswith("/jax/core/compile") else None)


def _require(n_shards):
    if N_DEV < n_shards:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV}")


# --------------------------------------------------------------------------
# direct _pop differential on crafted queue states
# --------------------------------------------------------------------------

def _mk_state(cfg, q_sid, q_seq, q_valid, q_ts=None):
    """Craft a raw queue state (stale slots, seq collisions and all)."""
    state = init_state(cfg)
    Q = cfg.queue
    assert len(q_sid) == Q
    ts = q_ts if q_ts is not None else np.arange(Q, dtype=np.int32)
    rng = np.random.default_rng(7)
    return state._replace(
        q_sid=jnp.asarray(np.asarray(q_sid, np.int32)),
        q_seq=jnp.asarray(np.asarray(q_seq, np.int32)),
        q_valid=jnp.asarray(np.asarray(q_valid, bool)),
        q_ts=jnp.asarray(np.asarray(ts, np.int32)),
        q_vals=jnp.asarray(rng.standard_normal(
            (Q, cfg.channels)).astype(np.float32)))


def _assert_pops_equal(state, prio, batch, tenant, weight):
    sA, pA = _pop(state, prio, batch, tenant, weight, "lexsort")
    sB, pB = _pop(state, prio, batch, tenant, weight, "packed")
    for a, b, name in zip(pA, pB, ("sid", "vals", "ts", "valid")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"popped {name}")
    np.testing.assert_array_equal(np.asarray(sA.q_valid),
                                  np.asarray(sB.q_valid))


def test_packed_matches_lexsort_deterministic():
    """Weighted interleave + a zero-weight tenant + stale slots whose seq
    collides, priorities including INT_MAX and negative values."""
    cfg = EngineConfig(n_streams=8, n_tenants=3, queue=12, batch=6)
    q_sid = [0, 1, 2, 3, 0, 1, 2, 3, 4, 5, 6, 7]
    q_seq = [1, 2, 3, 4, 5, 6, 7, 8, 0, 0, 0, 3]     # collisions on stale
    q_valid = [1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0]
    state = _mk_state(cfg, q_sid, q_seq, q_valid)
    prio = jnp.asarray([0, 0, 5, -3, 0, 2**31 - 1, 0, 1], jnp.int32)
    tenant = jnp.asarray([0, 1, 0, 1, 2, 2, 0, 1], jnp.int32)
    for weight in ([3, 1, 0], [0, 0, 0], [1, 1, 1], [2**15, 1, 5]):
        _assert_pops_equal(state, prio, cfg.batch, tenant,
                           jnp.asarray(weight, jnp.int32))


def test_packed_matches_lexsort_no_tenant_signature():
    cfg = EngineConfig(n_streams=4, queue=8, batch=8)
    state = _mk_state(cfg, [3, 1, 2, 0] * 2, [4, 1, 3, 2, 8, 7, 6, 5],
                      [1, 1, 0, 1, 1, 0, 1, 1])
    prio = jnp.asarray([1, 0, 2, 0], jnp.int32)
    sA, pA = _pop(state, prio, cfg.batch, scheduler="lexsort")
    sB, pB = _pop(state, prio, cfg.batch, scheduler="packed")
    for a, b in zip(pA, pB):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(sA.q_valid),
                                  np.asarray(sB.q_valid))


@st.composite
def _pop_states(draw):
    Q = draw(st.integers(2, 24))
    N = draw(st.integers(2, 10))
    T = draw(st.integers(1, 4))
    batch = draw(st.integers(1, Q))
    q_sid = [draw(st.integers(-1, N)) for _ in range(Q)]   # incl. clip range
    q_seq = [draw(st.integers(-3, 10)) for _ in range(Q)]  # collisions likely
    q_valid = [draw(st.booleans()) for _ in range(Q)]
    prio = [draw(st.sampled_from([0, 1, 2, 7, -5, 2**31 - 1]))
            for _ in range(N)]
    tenant = [draw(st.integers(-1, T)) for _ in range(N)]  # incl. clip range
    weight = [draw(st.sampled_from([0, 1, 2, 5, 2**15])) for _ in range(T)]
    return Q, N, T, batch, q_sid, q_seq, q_valid, prio, tenant, weight


@settings(max_examples=50, deadline=None)
@given(_pop_states())
def test_packed_matches_lexsort_property(case):
    Q, N, T, batch, q_sid, q_seq, q_valid, prio, tenant, weight = case
    cfg = EngineConfig(n_streams=N, n_tenants=T, queue=Q, batch=batch)
    state = _mk_state(cfg, q_sid, q_seq, q_valid)
    _assert_pops_equal(state, jnp.asarray(prio, jnp.int32), batch,
                       jnp.asarray(tenant, jnp.int32),
                       jnp.asarray(weight, jnp.int32))


def test_pallas_kernel_matches_ref_pop():
    """The fused Pallas kernel (interpret mode on CPU) returns the same
    winners, payload gathers included, as the jnp selection ref."""
    rng = np.random.default_rng(3)
    for Q, T, B, C in ((5, 2, 3, 1), (130, 3, 16, 4), (256, 1, 8, 2)):
        prio = jnp.asarray(rng.choice([0, 1, 5, 2**31 - 1, -2], Q)
                           .astype(np.int32))
        seq = jnp.asarray(rng.integers(-3, 40, Q).astype(np.int32))
        valid = jnp.asarray(rng.random(Q) < 0.6)
        tenant = jnp.asarray(rng.integers(0, T, Q).astype(np.int32))
        w = jnp.asarray(rng.choice([0, 1, 4, 2**15], T)
                        .astype(np.int32))[tenant]
        sid = jnp.asarray(rng.integers(0, 64, Q).astype(np.int32))
        ts = jnp.asarray(rng.integers(-2**31 + 1, 2**31 - 1, Q)
                         .astype(np.int32))
        v = rng.standard_normal((Q, C)).astype(np.float32)
        v[rng.random((Q, C)) < 0.2] = -0.0      # sign-of-zero must survive
        vals = jnp.asarray(v)
        tA, pA = sched_pop(prio, seq, valid, tenant, w, sid, vals, ts, B,
                           use_kernel=False)
        tB, pB = sched_pop(prio, seq, valid, tenant, w, sid, vals, ts, B,
                           use_kernel=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(tA), np.asarray(tB))
        for a, b, name in zip(pA, pB, ("sid", "vals", "ts", "valid")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"Q={Q} {name}")
        # assert_array_equal treats -0.0 == 0.0; the gather must be
        # *bitwise* identical (the fused kernel sums payload bits)
        np.testing.assert_array_equal(
            np.asarray(pA[1]).view(np.int32), np.asarray(pB[1]).view(np.int32),
            err_msg=f"Q={Q} payload bits (sign of zero)")


# --------------------------------------------------------------------------
# int32 virtual-tag boundary (the rank clamp): deep queue, weight 1
# --------------------------------------------------------------------------

def test_rank_clamp_boundary():
    """At weight 1 the virtual tag is ``rank * FAIR_SCALE``; past
    ``RANK_LIM`` (~64k) the unclamped product wraps int32 negative and a
    deep SU would jump the whole queue.  Both scheduler paths must clamp
    identically: FIFO order preserved at the boundary, and bit-identical
    to each other."""
    Q = RANK_LIM + 66          # deep enough to cross the clamp boundary
    cfg = EngineConfig(n_streams=2, n_tenants=2, channels=1,
                       queue=Q, batch=8)
    state = init_state(cfg)
    state, dropped = _enqueue(
        state, jnp.zeros((Q,), jnp.int32),
        jnp.zeros((Q, 1), jnp.float32),
        jnp.arange(Q, dtype=jnp.int32), jnp.ones((Q,), bool))
    assert int(dropped) == 0
    prio = jnp.zeros((2,), jnp.int32)
    tenant = jnp.zeros((2,), jnp.int32)
    weight = jnp.asarray([1, 0], jnp.int32)    # weight 1: maximal tags
    sA, pA = _pop(state, prio, cfg.batch, tenant, weight, "lexsort")
    sB, pB = _pop(state, prio, cfg.batch, tenant, weight, "packed")
    for a, b in zip(pA, pB):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # FIFO preserved: the *oldest* SUs pop first — an unclamped overflow
    # would hand negative tags to ranks > RANK_LIM and pop the tail
    assert np.asarray(pA[2]).tolist() == list(range(cfg.batch))
    # the clamp itself: the deepest rank's tag stays positive in int32
    # (RANK_LIM is one step conservative; two past it wraps negative)
    assert (RANK_LIM + 1) * FAIR_SCALE <= np.iinfo(np.int32).max
    assert (RANK_LIM + 2) * FAIR_SCALE > np.iinfo(np.int32).max  # why clamp
    assert sched_ref.RANK_LIM == RANK_LIM      # kernels mirror the constant
    assert sched_ref.FAIR_SCALE == FAIR_SCALE


# --------------------------------------------------------------------------
# engine-level differential: packed vs lexsort engines, 1 and 2 shards
# --------------------------------------------------------------------------

def _build_engine(scheduler, n_shards):
    cfg = EngineConfig(n_streams=16, n_tenants=4, batch=4, queue=64,
                       max_in=4, max_out=4, prog_len=24, n_temps=12,
                       n_shards=n_shards, scheduler=scheduler)
    reg = Registry.with_capacity(cfg)
    heavy = reg.create_tenant("heavy")
    light = reg.create_tenant("light")
    srcs = [reg.create_stream(heavy, f"h{i}", ["v"]) for i in range(3)]
    srcs.append(reg.create_stream(light, "l0", ["v"]))
    comps = [reg.create_composite(heavy, f"c{i}", ["v"], [srcs[i % 3]],
                                  {"v": f"in0.v + {i}"}) for i in range(6)]
    comps.append(reg.create_composite(light, "lc", ["v"], [srcs[3]],
                                      {"v": "in0.v * 2"}))
    eng = create_engine(reg)
    eng.set_weight(heavy, 3)
    eng.set_weight(light, 1)
    eng.set_quota(heavy, 2, 4)
    return eng, heavy, light, srcs


def _state_arrays(eng):
    st = eng.state
    out = {f: np.asarray(getattr(st, f))
           for f in ("values", "timestamps", "q_sid", "q_vals", "q_ts",
                     "q_seq", "q_valid", "seq", "tenant_emitted",
                     "tenant_queued")}
    out.update({f"stat.{k}": np.asarray(v) for k, v in st.stats.items()})
    return out


@pytest.mark.parametrize("n_shards", [1, 2])
def test_engine_bit_identical_across_schedulers(n_shards):
    """Same adversarial workload (weighted tenants, quota, fan-out
    backlog, same-ts ties) on a packed engine and a lexsort engine —
    every state leaf, stat and sink must match bit for bit, through
    rounds and a superstep."""
    _require(n_shards)
    engA = _build_engine("lexsort", n_shards)[0]
    engB = _build_engine("packed", n_shards)[0]
    for eng in (engA, engB):
        srcs = [eng.registry.streams[i] for i in range(4)]
        ts = 1
        for w in range(4):
            for s in srcs:
                eng.post(s, [float(w)], ts)
            eng.post(srcs[0], [9.0], ts)       # same-stream burst
            sinkA = eng.round()
            ts += 2
        eng.drain(max_rounds=8)
        for s in srcs:
            eng.post(s, [5.0], ts)
        eng.superstep(3)
    a, b = _state_arrays(engA), _state_arrays(engB)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"leaf {k}")
    assert engA.counters() == engB.counters()


# --------------------------------------------------------------------------
# zero-retrace across live QoS knob churn on the packed path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2])
def test_packed_sched_zero_retrace_across_knob_churn(n_shards):
    _require(n_shards)
    eng, heavy, light, srcs = _build_engine("packed", n_shards)
    K = 2
    step0 = compile_count(eng._step)
    scan0 = compile_count(eng._superstep_fn(K))
    eng.post(srcs[0], [1.0], 1)
    eng.round()
    eng.superstep(K)
    jax.block_until_ready(eng.state.timestamps)
    cache_step = compile_count(eng._step) - step0
    cache_scan = compile_count(eng._superstep_fns[K]) - scan0
    n_traces = len(_TRACES)
    ts = 10
    for r in range(5):
        eng.set_weight(heavy, 1 + r)
        eng.set_weight(light, 5 - r)
        eng.set_quota(heavy, 1 + r % 2)
        for s in srcs:
            eng.post(s, [float(r)], ts)
        eng.round() if r % 2 else eng.superstep(K)
        ts += K + 1
    jax.block_until_ready(eng.state.timestamps)
    assert compile_count(eng._step) - step0 == cache_step == 1
    assert compile_count(eng._superstep_fns[K]) - scan0 == cache_scan == 1
    assert len(_TRACES) == n_traces, \
        f"packed-scheduler knob churn recompiled: {_TRACES[n_traces:]}"
