"""Fused-round differential tests: the fused path (round_fuse stages 1-3
in one operation) must be bit-identical to the staged round through whole
engine histories — single and sharded, per-round and superstep — must
never retrace under QoS/admission churn, and must fall back to the staged
path exactly when a non-fusable (transcendental) program is installed.
Also pins the drop-accounting fixes that rode along: the DLQ tenant
sentinel (-1, not tenant 0) and its round-trip through redeliver()."""
from typing import Optional

import numpy as np
import pytest

try:        # the hypothesis differential skips without it; the fixed-seed
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

        @staticmethod
        def data():
            return None

import jax.numpy as jnp

from repro.core import EngineConfig, Registry, create_engine
from repro.launch.compiles import compile_count


# --------------------------------------------------------------------------
# engine-history differential harness
# --------------------------------------------------------------------------

def _build(fused: bool, n_shards: int = 1, superstep: int = 1, seed: int = 0,
           dlq: int = 16):
    cfg = EngineConfig(n_streams=64, n_tenants=4, channels=3, max_in=4,
                       max_out=4, batch=8, queue=128, prog_len=16,
                       n_consts=8, n_temps=8, sink_buffer=32,
                       dlq_slots=dlq, retention_slots=2,
                       n_shards=n_shards, superstep=superstep,
                       fused_round=fused).validate()
    reg = Registry(cfg)
    t0 = reg.create_tenant("a")
    t1 = reg.create_tenant("b")
    srcs = [reg.create_stream(t0, f"s{i}", ["x", "y", "z"])
            for i in range(6)]
    c0 = reg.create_composite(t0, "c0", ["x", "y", "z"], srcs[:3],
                              {"x": "s0.x + s1.y", "y": "out.y + 1",
                               "z": "min(s2.z, 4.0)"},
                              post_filter="out.x < 100")
    reg.create_composite(t1, "c1", ["x", "y", "z"], [srcs[3], c0],
                         {"x": "c0.x * 2", "y": "s3.y - c0.z",
                          "z": "abs(s3.z)"})
    eng = create_engine(reg)
    return eng, (t0, t1), srcs, c0


def _run(eng, srcs, rounds: int, seed: int, superstep: int = 1):
    rng = np.random.default_rng(seed)
    sinks = []
    for r in range(rounds):
        for s in srcs:
            if rng.random() < 0.8:
                eng.post(s, rng.standard_normal(3).tolist(),
                         r * 10 + int(rng.integers(0, 9)))
        if superstep > 1:
            for sp in eng.drain_spools(superstep, max_rounds=superstep):
                sinks.extend(eng.spool_sinks(sp))
        else:
            sinks.append(eng.round())
    return sinks


def _arrs(eng, sinks):
    from repro.core.engine import EngineState
    out = {}
    for f in EngineState._fields:
        if f == "stats":
            for k, v in eng.state.stats.items():
                out[f"stats/{k}"] = np.asarray(v)
        else:
            out[f"state/{f}"] = np.asarray(getattr(eng.state, f))
    for i, s in enumerate(sinks):
        out[f"sink{i}/sid"] = np.asarray(s.sid)
        out[f"sink{i}/vals"] = np.asarray(s.vals)
        out[f"sink{i}/valid"] = np.asarray(s.valid)
    return out


def _assert_bitwise(a, b):
    assert a.keys() == b.keys()
    for k in a:
        x, y = a[k], b[k]
        assert x.shape == y.shape, k
        np.testing.assert_array_equal(
            x.view(np.int32) if x.dtype == np.float32 else x,
            y.view(np.int32) if y.dtype == np.float32 else y,
            err_msg=k)


@pytest.mark.parametrize("n_shards,superstep",
                         [(1, 1), (1, 3), (2, 1), (2, 3)])
def test_fused_bit_identical_to_staged(n_shards, superstep):
    """Whole-history differential: every state leaf, stat and sink of the
    fused engine matches the staged engine bitwise (float32 compared in
    bit space, so -0.0 and NaN payloads count too)."""
    e0, _, srcs0, _ = _build(False, n_shards, superstep)
    e1, _, srcs1, _ = _build(True, n_shards, superstep)
    assert e0._path == "staged" and e1._path == "fused"
    s0 = _run(e0, srcs0, 12, seed=7, superstep=superstep)
    s1 = _run(e1, srcs1, 12, seed=7, superstep=superstep)
    _assert_bitwise(_arrs(e0, s0), _arrs(e1, s1))


def test_fused_zero_retrace_under_churn():
    """The retrace contract holds on the fused path: weight/quota edits,
    admission, revocation and program swaps (to fusable programs) are all
    table edits — the compiled step compiles exactly once."""
    eng, (t0, t1), srcs, c0 = _build(True)
    assert eng._path == "fused"
    step0 = compile_count(eng._step)
    _run(eng, srcs, 2, seed=1)
    cache0 = compile_count(eng._step) - step0
    assert cache0 == 1

    eng.set_weight(t0, 5)
    eng.set_quota(t1, 100, burst=200)
    _run(eng, srcs, 1, seed=2)
    s_new = eng.admit_stream(t0, "late", ["x", "y", "z"], priority=1)
    c_new = eng.admit_composite(t1, "lc", ["x", "y", "z"], [s_new, srcs[0]],
                                {"x": "late.x - s0.y", "y": "out.y * 0.5",
                                 "z": "max(late.z, 0.0)"})
    _run(eng, srcs + [s_new], 2, seed=3)
    eng.swap_program(c_new, {"x": "late.x", "y": "0.0", "z": "s0.z + 1"})
    eng.revoke_stream(c_new)
    eng.set_weight(t0, 0)
    eng.set_quota(t1, 0)
    _run(eng, srcs + [s_new], 2, seed=4)

    assert eng._path == "fused"
    assert compile_count(eng._step) - step0 == cache0 == 1


def test_fallback_flips_on_transcendental_swap():
    """Installing a transcendental program flips the engine to the staged
    path (still bit-identical to an always-staged engine); swapping back
    to fusable code returns to the fused path."""
    e0, _, srcs0, c0_0 = _build(False)
    e1, _, srcs1, c0_1 = _build(True)
    s0 = _run(e0, srcs0, 3, seed=11)
    s1 = _run(e1, srcs1, 3, seed=11)

    hot = {"x": "exp(s0.x)", "y": "out.y + 1", "z": "min(s2.z, 4.0)"}
    e0.swap_program(c0_0, hot, post_filter="out.x < 100")
    e1.swap_program(c0_1, hot, post_filter="out.x < 100")
    assert e1._path == "staged"          # exp is not fusable
    s0 += _run(e0, srcs0, 3, seed=12)
    s1 += _run(e1, srcs1, 3, seed=12)

    cool = {"x": "s0.x + s1.y", "y": "out.y + 1", "z": "min(s2.z, 4.0)"}
    e0.swap_program(c0_0, cool, post_filter="out.x < 100")
    e1.swap_program(c0_1, cool, post_filter="out.x < 100")
    assert e1._path == "fused"
    s0 += _run(e0, srcs0, 3, seed=13)
    s1 += _run(e1, srcs1, 3, seed=13)

    _assert_bitwise(_arrs(e0, s0), _arrs(e1, s1))
    assert e0._path == "staged"          # fused_round=False never fuses


def test_revoked_rows_stay_fusable():
    """Revocation clears the row's program to NOPs, so revoking the only
    non-fusable stream returns the engine to the fused path."""
    eng, (t0, t1), srcs, c0 = _build(True)
    hot = eng.admit_composite(t1, "hot", ["x", "y", "z"], [srcs[4]],
                              {"x": "log(s4.x)", "y": "s4.y", "z": "s4.z"})
    assert eng._path == "staged"
    eng.revoke_stream(hot)
    assert eng._path == "fused"
    _run(eng, srcs, 2, seed=5)


# --------------------------------------------------------------------------
# DLQ tenant sentinel (drop-accounting bugfix)
# --------------------------------------------------------------------------

def test_dlq_unknown_tenant_records_sentinel():
    """``dlq_append(tenant=None)`` must record -1 (owner unknown), not
    charge tenant 0, and the sentinel must round-trip through
    ``dead_letters()`` and ``redeliver()`` without corrupting any
    per-tenant counter (-1 would otherwise wrap to the *last* tenant in
    ``.at[]`` updates)."""
    from repro.core.engine import DLQ_OVERFLOW, dlq_append

    eng, (t0, t1), srcs, c0 = _build(True)
    sid = jnp.full((2,), srcs[0].sid, jnp.int32)
    vals = jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]], jnp.float32)
    ts = jnp.asarray([3, 4], jnp.int32)
    eng.state = dlq_append(eng.state, sid, vals, ts, None, DLQ_OVERFLOW,
                           jnp.asarray([True, True]))

    letters = eng.dead_letters(clear=True)
    assert [lt.tenant for lt in letters] == [-1, -1]
    assert [lt.reason for lt in letters] == ["overflow", "overflow"]

    charged_before = np.asarray(eng.state.tenant_dropped_overflow).copy()
    queued_before = int(np.asarray(eng.state.q_valid).sum())
    assert eng.redeliver(letters) == 2
    # sentinel letters re-enqueue (requeue path, not tenant-0 ingest) ...
    assert int(np.asarray(eng.state.q_valid).sum()) == queued_before + 2
    eng.round()
    # ... and no per-tenant overflow counter moved: -1 is chargeable to
    # nobody, and must not wrap onto the last tenant
    np.testing.assert_array_equal(
        np.asarray(eng.state.tenant_dropped_overflow), charged_before)


def test_enqueue_overflow_without_tenant_charges_nobody():
    """An overflow drop with the -1 sentinel must not wrap onto the last
    tenant's drop counter (the ``.at[]`` negative-index wrap bug)."""
    from repro.core.engine import _enqueue, init_state

    cfg = EngineConfig(n_streams=8, n_tenants=3, channels=1, max_in=2,
                       max_out=2, batch=2, queue=2, prog_len=4,
                       n_consts=2, n_temps=2, sink_buffer=4,
                       dlq_slots=4).validate()
    state = init_state(cfg)
    sid = jnp.asarray([1, 2, 3, 4], jnp.int32)
    vals = jnp.ones((4, 1), jnp.float32)
    ts = jnp.asarray([1, 1, 1, 1], jnp.int32)
    mask = jnp.ones((4,), bool)
    state, dropped = _enqueue(state, sid, vals, ts, mask,
                              tenant=jnp.full((4,), -1, jnp.int32))
    assert int(dropped) == 2                            # queue holds 2 of 4
    # per-tenant shed counters untouched: the sentinel lands in the
    # overflow pad row (index T), not tenant T-1 via negative-index wrap
    np.testing.assert_array_equal(np.asarray(state.tenant_dropped_overflow),
                                  np.zeros(cfg.n_tenants, np.int32))
    # drop-class accounting reaches the DLQ with the sentinel preserved
    np.testing.assert_array_equal(np.asarray(state.dlq_tenant[:2]),
                                  np.full(2, -1, np.int32))


# --------------------------------------------------------------------------
# hypothesis ref-level differential (skips without hypothesis)
# --------------------------------------------------------------------------

def _ref_case(prio, seq, valid, tenant, weight, sid, ts, payload_bits,
              revoked, retired):
    """Assemble one differential case from drawn primitives."""
    Q, N, B, F, M, C, L = 24, 12, 4, 3, 3, 2, 6
    rng = np.random.default_rng(payload_bits)
    prio = np.asarray(prio, np.int32)
    vals = rng.standard_normal((Q, C)).astype(np.float32)
    vals.ravel()[rng.integers(0, Q * C, 2)] = [np.inf, -0.0]
    q_valid = np.asarray(valid, bool)
    q_valid[retired % Q] = False                         # retired slot
    out_table = rng.integers(-1, N, (N, F)).astype(np.int32)
    in_table = rng.integers(-1, N, (N, M)).astype(np.int32)
    active = np.ones(N, bool)
    active[revoked % N] = False                          # revoked row
    return dict(Q=Q, N=N, B=B, F=F, M=M, C=C, L=L, rng=rng,
                prio=prio, seq=np.asarray(seq, np.int32), q_valid=q_valid,
                tenant=np.asarray(tenant, np.int32),
                weight=np.asarray(weight, np.int32),
                sid=np.asarray(sid, np.int32), vals=vals,
                ts=np.asarray(ts, np.int32), out_table=out_table,
                in_table=in_table, active=active)


def _check_ref_vs_staged(c):
    from repro.core import program as pvm
    from repro.core.engine import fanout_reference, process_work_items
    from repro.kernels.round_fuse import ref as rfr

    Q, N, B, F, C, L = c["Q"], c["N"], c["B"], c["F"], c["C"], c["L"]
    rng = c["rng"]
    cfg = EngineConfig(n_streams=N, n_tenants=4, channels=C,
                       max_in=c["M"], max_out=F, batch=B, queue=Q,
                       prog_len=L, n_consts=4, n_temps=4).validate()
    layout = rfr.RegLayout.from_cfg(cfg)
    ops_pool = np.asarray(sorted(rfr.FUSABLE_OPS), np.int32)
    progs = np.stack([rng.choice(ops_pool, (N, L)),
                      rng.integers(0, layout.n_regs, (N, L)),
                      rng.integers(0, layout.n_regs, (N, L)),
                      rng.integers(0, layout.n_regs, (N, L))],
                     axis=-1).astype(np.int32)
    consts = rng.standard_normal((N, 4)).astype(np.float32)
    is_comp = rng.random(N) < 0.8
    values = rng.standard_normal((N, C)).astype(np.float32)
    timestamps = rng.integers(-5, 30, N).astype(np.int32)
    j = lambda x: jnp.asarray(x)
    w_slot = c["weight"][np.clip(c["tenant"], 0, 3)]

    take, pop, wi = rfr.pop_dispatch_ref(
        j(c["prio"]), j(c["seq"]), j(c["q_valid"]),
        j(np.clip(c["tenant"], 0, 3)), j(w_slot), j(c["sid"]), j(c["vals"]),
        j(c["ts"]), B, j(c["out_table"]), j(c["active"]))
    wi_t, wi_src, wi_vals, wi_ts = wi
    rows = jnp.clip(wi_t, 0, N - 1)
    fused = rfr.apply_programs_ref(
        layout, j(c["in_table"]), j(progs), j(consts), j(is_comp),
        j(c["active"]), rows, rows, wi_src, wi_vals, wi_ts, wi_t >= 0,
        j(values), j(timestamps))

    # staged composition over the identical pop winners
    e_sid, e_vals, e_ts, e_pop, e_act = pop
    targets, _ = fanout_reference(e_sid, e_ts, e_pop & e_act,
                                  j(c["out_table"]), j(timestamps),
                                  with_early=False)
    s_wt = targets.reshape(B * F)
    np.testing.assert_array_equal(np.asarray(wi_t), np.asarray(s_wt))

    from types import SimpleNamespace
    tbl = SimpleNamespace(in_table=j(c["in_table"]), progs=j(progs),
                          consts=j(consts), is_composite=j(is_comp),
                          active=j(c["active"]))

    s_rows = jnp.clip(s_wt, 0, N - 1)
    staged = process_work_items(
        cfg, tbl, s_rows, s_rows, jnp.repeat(e_sid, F),
        jnp.repeat(e_vals, F, axis=0), jnp.repeat(e_ts, F), s_wt >= 0,
        j(values), j(timestamps))

    new_vals, ts_out, live, keep, keep_ts, passf, badf = fused
    s_new_vals, s_ts_out, s_live, s_keep, counts, s_badf = staged
    np.testing.assert_array_equal(np.asarray(new_vals).view(np.int32),
                                  np.asarray(s_new_vals).view(np.int32))
    np.testing.assert_array_equal(np.asarray(ts_out), np.asarray(s_ts_out))
    np.testing.assert_array_equal(np.asarray(live), np.asarray(s_live))
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(s_keep))
    # the poison detector itself is part of the differential contract
    np.testing.assert_array_equal(np.asarray(badf), np.asarray(s_badf))
    assert int(counts["processed"]) == int(live.sum())
    assert int(counts["discarded_stale"]) == int((live & ~keep_ts).sum())
    assert int(counts["filtered"]) == int((live & keep_ts & ~passf).sum())
    assert int(counts["nonfinite"]) == int((badf & (wi_t >= 0)).sum())


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_ref_differential_hypothesis(data):
    Q = 24
    d = lambda lo, hi, n: data.draw(st.lists(st.integers(lo, hi),
                                             min_size=n, max_size=n))
    c = _ref_case(
        prio=d(0, 3, Q), seq=d(-5, 50, Q),
        valid=[v == 1 for v in d(0, 1, Q)],
        tenant=d(0, 3, Q), weight=d(0, 9, 4),
        sid=d(0, 15, Q),                    # some out-of-range (N=12)
        ts=d(-20, 40, Q),
        payload_bits=data.draw(st.integers(0, 2**31 - 1)),
        revoked=data.draw(st.integers(0, 11)),
        retired=data.draw(st.integers(0, 23)))
    _check_ref_vs_staged(c)


@pytest.mark.parametrize("seed", range(6))
def test_ref_differential_fixed(seed):
    """Deterministic differential cases — the same check hypothesis runs,
    alive even without hypothesis installed."""
    rng = np.random.default_rng(100 + seed)
    Q = 24
    c = _ref_case(
        prio=rng.integers(0, 4, Q), seq=rng.integers(-5, 50, Q),
        valid=rng.random(Q) < 0.7, tenant=rng.integers(0, 4, Q),
        weight=rng.integers(0, 10, 4), sid=rng.integers(0, 16, Q),
        ts=rng.integers(-20, 40, Q),
        payload_bits=int(rng.integers(0, 2**31 - 1)),
        revoked=int(rng.integers(0, 12)), retired=int(rng.integers(0, 24)))
    _check_ref_vs_staged(c)
