"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp/numpy oracles, executed with interpret=True on CPU."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm_chunk.ops import mlstm_pallas
from repro.kernels.mlstm_chunk.ref import mlstm_ref
from repro.kernels.sched_pop.kernel import sched_pop_call
from repro.kernels.sched_pop.ref import sched_pop_ref
from repro.kernels.selective_scan.ops import ssm_scan_pallas
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.kernels.stream_dispatch.kernel import onehot_gather
from repro.kernels.stream_dispatch.ops import stream_dispatch
from repro.kernels.stream_dispatch.ref import (onehot_gather_ref,
                                               stream_dispatch_ref)
from repro.kernels.window_agg.ops import window_agg_op
from repro.kernels.window_agg.ref import window_agg_ref

RNG = np.random.default_rng(0)


# --------------------------------------------------------------- dispatch
@pytest.mark.parametrize("N,F,B", [(64, 4, 16), (300, 7, 33), (1024, 16, 256),
                                   (128, 1, 8)])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_onehot_gather_sweep(N, F, B, dtype):
    table = RNG.integers(-3, 1000, size=(N, F)).astype(dtype)
    ids = RNG.integers(-2, N + 2, size=(B,)).astype(np.int32)
    got = onehot_gather(jnp.asarray(table), jnp.asarray(ids), interpret=True)
    want = onehot_gather_ref(jnp.asarray(table), jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)


@pytest.mark.parametrize("N,F,B", [(64, 4, 16), (256, 16, 64)])
def test_stream_dispatch_sweep(N, F, B):
    table = RNG.integers(-1, N, size=(N, F)).astype(np.int32)
    ids = RNG.integers(0, N, size=(B,)).astype(np.int32)
    ts = RNG.integers(-2**31 + 1, 2**31 - 1, size=(B,)).astype(np.int32)
    tstab = RNG.integers(-2**31 + 1, 2**31 - 1, size=(N,)).astype(np.int32)
    valid = RNG.random(B) > 0.3
    tg, ea = stream_dispatch(jnp.asarray(ids), jnp.asarray(ts),
                             jnp.asarray(valid), jnp.asarray(table),
                             jnp.asarray(tstab), interpret=True)
    tg2, ea2 = stream_dispatch_ref(jnp.asarray(ids), jnp.asarray(ts),
                                   jnp.asarray(valid), jnp.asarray(table),
                                   jnp.asarray(tstab))
    np.testing.assert_array_equal(np.asarray(tg), np.asarray(tg2))
    np.testing.assert_array_equal(np.asarray(ea), np.asarray(ea2))


# -------------------------------------------------------------- sched pop
@pytest.mark.parametrize("Q,T,B,C", [(4, 1, 2, 1), (64, 4, 16, 4),
                                     (300, 3, 24, 2), (1024, 8, 64, 4)])
def test_sched_pop_sweep(Q, T, B, C):
    prio = RNG.choice([0, 1, 3, 2**31 - 1, -4], Q).astype(np.int32)
    seq = RNG.integers(-5, 60, Q).astype(np.int32)      # collisions likely
    valid = RNG.random(Q) < 0.6
    tenant = RNG.integers(0, T, Q).astype(np.int32)
    w_slot = RNG.choice([0, 1, 2, 7, 2**15], T).astype(np.int32)[tenant]
    sid = RNG.integers(0, 2**24, Q).astype(np.int32)
    ts = RNG.integers(-2**31 + 1, 2**31 - 1, Q).astype(np.int32)
    vals = RNG.standard_normal((Q, C)).astype(np.float32)
    args = tuple(map(jnp.asarray, (prio, seq, valid, tenant, w_slot)))
    want = sched_pop_ref(*args, B)
    got, popped = sched_pop_call(*args, jnp.asarray(sid), jnp.asarray(vals),
                                 jnp.asarray(ts), B, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    take = np.asarray(want)
    np.testing.assert_array_equal(np.asarray(popped[0]), sid[take])
    np.testing.assert_array_equal(np.asarray(popped[1]), vals[take])
    np.testing.assert_array_equal(np.asarray(popped[2]), ts[take])
    np.testing.assert_array_equal(np.asarray(popped[3]), valid[take])


# ------------------------------------------------------------- attention
@pytest.mark.parametrize("B,H,KV,L,Dh,win,blk", [
    (1, 2, 2, 128, 64, None, 64),
    (2, 4, 2, 256, 128, None, 128),
    (1, 4, 1, 256, 64, 64, 64),
    (2, 2, 2, 128, 32, 32, 64),
    (1, 8, 4, 128, 64, None, 32),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, L, Dh, win, blk, dtype):
    q = RNG.standard_normal((B, H, L, Dh)).astype(np.float32)
    k = RNG.standard_normal((B, KV, L, Dh)).astype(np.float32)
    v = RNG.standard_normal((B, KV, L, Dh)).astype(np.float32)
    qj, kj, vj = (jnp.asarray(x).astype(dtype) for x in (q, k, v))
    got = flash_attention(qj, kj, vj, causal=True, window=win,
                          blk_q=blk, blk_k=blk, interpret=True)
    want = attention_ref(qj.astype(jnp.float32), kj.astype(jnp.float32),
                         vj.astype(jnp.float32), causal=True, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


# -------------------------------------------------------- selective scan
@pytest.mark.parametrize("B,L,Di,S,bt,bd", [
    (1, 16, 32, 8, 8, 16), (2, 64, 128, 16, 16, 64), (1, 128, 256, 16, 32, 128),
])
def test_selective_scan_sweep(B, L, Di, S, bt, bd):
    a = np.exp(-np.abs(RNG.standard_normal((B, L, Di, S)))).astype(np.float32)
    bx = RNG.standard_normal((B, L, Di, S)).astype(np.float32)
    c = RNG.standard_normal((B, L, S)).astype(np.float32)
    h0 = RNG.standard_normal((B, Di, S)).astype(np.float32)
    y, h = ssm_scan_pallas(jnp.asarray(a), jnp.asarray(bx), jnp.asarray(c),
                           jnp.asarray(h0), blk_t=bt, blk_d=bd, interpret=True)
    yr, hr = selective_scan_ref(a, bx, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-4,
                               atol=1e-4)


# ----------------------------------------------------------------- mLSTM
@pytest.mark.parametrize("B,H,L,Dh,ck", [
    (1, 2, 32, 16, 8), (2, 2, 64, 32, 16), (1, 4, 128, 64, 32),
    (1, 1, 64, 128, 64),
])
def test_mlstm_chunkwise_sweep(B, H, L, Dh, ck):
    q = RNG.standard_normal((B, H, L, Dh)).astype(np.float32)
    k = RNG.standard_normal((B, H, L, Dh)).astype(np.float32)
    v = RNG.standard_normal((B, H, L, Dh)).astype(np.float32)
    ir = RNG.standard_normal((B, H, L)).astype(np.float32)
    fr = (RNG.standard_normal((B, H, L)) + 2).astype(np.float32)
    h, (C, n, m) = mlstm_pallas(*map(jnp.asarray, (q, k, v, ir, fr)),
                                chunk=ck, interpret=True)
    C0 = np.zeros((B, H, Dh, Dh), np.float32)
    n0 = np.zeros((B, H, Dh), np.float32)
    m0 = np.full((B, H), -1e30, np.float32)
    hr, (Cr, nr, mr) = mlstm_ref(q, k, v, ir, fr, C0, n0, m0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=3e-4,
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(C), np.asarray(Cr), rtol=3e-4,
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(n), np.asarray(nr), rtol=3e-4,
                               atol=3e-4)


# ------------------------------------------------------------ window agg
@pytest.mark.parametrize("N,W,C", [(8, 4, 2), (64, 16, 4), (100, 8, 3),
                                   (256, 32, 1)])
def test_window_agg_sweep(N, W, C):
    vals = RNG.standard_normal((N, W, C)).astype(np.float32)
    count = RNG.integers(0, W + 1, N).astype(np.int32)
    got = window_agg_op(jnp.asarray(vals), jnp.asarray(count), interpret=True)
    want = window_agg_ref(jnp.asarray(vals), jnp.asarray(count))
    for key in want:
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want[key]),
                                   rtol=1e-6, atol=1e-6)


# ------------------------------------------------------------- round fuse
def _rf_modules():
    from repro.kernels.round_fuse import kernel as rfk
    from repro.kernels.round_fuse import ref as rfr
    return rfk, rfr


def _rf_layout(N, C, M, F, B, Q, L, K):
    from repro.core import EngineConfig
    from repro.kernels.round_fuse.ref import RegLayout
    cfg = EngineConfig(n_streams=N, channels=C, max_in=M, max_out=F,
                       batch=B, queue=Q, prog_len=L, n_consts=K, n_temps=4)
    return RegLayout.from_cfg(cfg)


def _rf_case(Q, N, C, B, F, M, L, K, T, seed):
    """One adversarial fused-round input set: out-of-range sids, retired
    slots, revoked rows, inf/NaN/-0.0 payloads, random fusable bytecode."""
    rfk, rfr = _rf_modules()
    rng = np.random.default_rng(seed)
    layout = _rf_layout(N, C, M, F, B, Q, L, K)
    prio = rng.choice([0, 1, 3, 2**31 - 1], Q).astype(np.int32)
    seq = rng.integers(-5, 60, Q).astype(np.int32)
    valid = rng.random(Q) < 0.6
    tenant = rng.integers(0, T, Q).astype(np.int32)
    w_slot = rng.choice([0, 1, 2, 7, 2**15], T).astype(np.int32)[tenant]
    sid = rng.integers(0, N + 4, Q).astype(np.int32)    # some out-of-range
    vals = rng.standard_normal((Q, C)).astype(np.float32)
    vals.ravel()[rng.integers(0, Q * C, 3)] = [np.inf, -0.0, np.nan]
    ts = rng.integers(-50, 50, Q).astype(np.int32)
    out_table = rng.integers(-1, N, (N, F)).astype(np.int32)
    in_table = rng.integers(-2, N, (N, M)).astype(np.int32)
    is_comp = rng.random(N) < 0.7
    active = rng.random(N) < 0.8
    values = rng.standard_normal((N, C)).astype(np.float32)
    values.ravel()[rng.integers(0, N * C, 2)] = [np.nan, -0.0]
    timestamps = rng.integers(-5, 40, N).astype(np.int32)
    R = layout.n_regs
    ops_pool = np.asarray(sorted(rfr.FUSABLE_OPS), np.int32)
    progs = np.stack([rng.choice(ops_pool, (N, L)),
                      rng.integers(0, R, (N, L)),
                      rng.integers(0, R, (N, L)),
                      rng.integers(0, R, (N, L))], axis=-1).astype(np.int32)
    consts = rng.standard_normal((N, K)).astype(np.float32)
    return layout, dict(
        prio=prio, seq=seq, valid=valid, tenant=tenant, w_slot=w_slot,
        sid=sid, vals=vals, ts=ts, out_table=out_table, in_table=in_table,
        is_comp=is_comp, active=active, values=values,
        timestamps=timestamps, progs=progs, consts=consts)


def _bits_equal(name, a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, name
    np.testing.assert_array_equal(
        a.view(np.int32) if a.dtype == np.float32 else a,
        b.view(np.int32) if b.dtype == np.float32 else b,
        err_msg=name)


@pytest.mark.parametrize("Q,N,C,B,F,M,L", [
    (32, 16, 1, 2, 2, 2, 4), (64, 24, 3, 4, 5, 6, 10),
    (200, 40, 4, 8, 3, 4, 12),
    # batch == Q: the valid slots run out mid-loop and every slot is
    # popped, so the inf/NaN/-0.0 payloads and (N = 8) a third of the
    # sids, clipped to the last row, reach the gathers
    (128, 8, 4, 128, 2, 2, 6),
    # batch above the ~58 valid slots, one subscriber per row
    (96, 12, 2, 80, 1, 3, 8),
    # queue and row tables that are not a whole number of the gathers'
    # 512-lane chunks (2,304 = 4 * 512 + 256 slots, 640 = 512 + 128 rows)
    (2300, 600, 4, 64, 3, 2, 8)])
def test_fused_round_kernel_sweep(Q, N, C, B, F, M, L):
    rfk, rfr = _rf_modules()
    K, T = 8, 4
    layout, c = _rf_case(Q, N, C, B, F, M, L, K, T, seed=Q + N)
    j = {k: jnp.asarray(v) for k, v in c.items()}
    take_r, pop_r, wi_r = rfr.pop_dispatch_ref(
        j["prio"], j["seq"], j["valid"], j["tenant"], j["w_slot"],
        j["sid"], j["vals"], j["ts"], B, j["out_table"], j["active"])
    rows = jnp.clip(wi_r[0], 0, N - 1)
    app_r = rfr.apply_programs_ref(
        layout, j["in_table"], j["progs"], j["consts"], j["is_comp"],
        j["active"], rows, rows, wi_r[1], wi_r[2], wi_r[3], wi_r[0] >= 0,
        j["values"], j["timestamps"])
    take_k, pop_k, wit_k, app_k = rfk.fused_round_call(
        j["prio"], j["seq"], j["valid"], j["tenant"], j["w_slot"],
        j["sid"], j["vals"], j["ts"], B, j["out_table"], j["in_table"],
        j["progs"], j["consts"], j["is_comp"], j["active"], j["values"],
        j["timestamps"], layout, interpret=True)
    _bits_equal("take", take_r, take_k)
    for i, nm in enumerate(["e_sid", "e_vals", "e_ts", "e_pop", "e_act"]):
        _bits_equal(nm, pop_r[i], pop_k[i])
    _bits_equal("wi_t", wi_r[0], wit_k)
    for i, nm in enumerate(["new_vals", "ts_out", "live", "keep",
                            "keep_ts", "passf", "badf"]):
        _bits_equal(nm, app_r[i], app_k[i])
    # the standalone apply kernel (the sharded round's post-exchange half)
    app_s = rfk.apply_programs_call(
        layout, j["in_table"], j["progs"], j["consts"], j["is_comp"],
        j["active"], rows, rows, wi_r[1], wi_r[2], wi_r[3], wi_r[0] >= 0,
        j["values"], j["timestamps"], interpret=True)
    for i, nm in enumerate(["new_vals", "ts_out", "live", "keep",
                            "keep_ts", "passf", "badf"]):
        _bits_equal(f"apply/{nm}", app_r[i], app_s[i])


@pytest.mark.parametrize("W,D,E,C", [(8, 1, 3, 2), (40, 4, 5, 3),
                                     (64, 2, 64, 4), (128, 8, 2, 1)])
def test_exchange_compact_kernel_sweep(W, D, E, C):
    rfk, rfr = _rf_modules()
    rng = np.random.default_rng(W * D + E)
    wi_t = rng.integers(-1, 30, W).astype(np.int32)
    wi_src = rng.integers(0, 30, W).astype(np.int32)
    wi_ts = rng.integers(-50, 50, W).astype(np.int32)
    wi_its = rng.integers(0, 100, W).astype(np.int32)
    wi_vals = rng.standard_normal((W, C)).astype(np.float32)
    wi_vals.ravel()[rng.integers(0, W * C, 2)] = [-0.0, np.inf]
    dest = np.where(wi_t >= 0, rng.integers(0, D, W), D).astype(np.int32)
    ref = rfr.exchange_compact_ref(
        *map(jnp.asarray, (wi_t, wi_src, wi_ts, wi_its, wi_vals, dest)),
        D, E)
    got = rfk.exchange_compact_call(
        *map(jnp.asarray, (wi_t, wi_src, wi_ts, wi_its, wi_vals, dest)),
        D, E, interpret=True)
    for i, nm in enumerate(["xi", "xf", "x_drop"]):
        _bits_equal(nm, ref[i], got[i])


def test_reduced_vm_matches_full_vm_on_fusable_ops():
    from repro.core import program as pvm
    rfk, rfr = _rf_modules()
    rng = np.random.default_rng(7)
    Wb, L, K, R = 16, 24, 8, 40
    ops_pool = np.asarray(sorted(rfr.FUSABLE_OPS), np.int32)
    progs = np.stack([rng.choice(ops_pool, (Wb, L)),
                      rng.integers(0, R, (Wb, L)),
                      rng.integers(0, R, (Wb, L)),
                      rng.integers(0, R, (Wb, L))], axis=-1).astype(np.int32)
    consts = rng.standard_normal((Wb, K)).astype(np.float32)
    regs = rng.standard_normal((Wb, R)).astype(np.float32)
    full = pvm.execute_batch(jnp.asarray(progs), jnp.asarray(consts),
                             jnp.asarray(regs))
    red = rfr.execute_batch_fused(jnp.asarray(progs), jnp.asarray(consts),
                                  jnp.asarray(regs))
    _bits_equal("vm", full, red)


@pytest.mark.parametrize("Q,X", [(16, 1), (64, 5), (64, 64), (100, 130)])
def test_first_free_slots_matches_nonzero(Q, X):
    _, rfr = _rf_modules()
    rng = np.random.default_rng(Q + X)
    for density in (0.0, 0.5, 0.95, 1.0):
        qv = jnp.asarray(rng.random(Q) < density)
        got = rfr.first_free_slots(qv, X)
        want = jnp.nonzero(~qv, size=X, fill_value=Q)[0].astype(jnp.int32)
        _bits_equal(f"ff[{density}]", got, want)
