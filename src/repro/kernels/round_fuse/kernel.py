"""Pallas TPU kernels: the fused engine round (stages 1-3) and the
sharded exchange compaction.

``_fused_round_kernel`` keeps one round's winners in VMEM end to end:
the ``sched_pop`` selection loop picks the top-``batch`` queue slots,
one winner per step, carrying only what decides the next winner (the
key planes, the taken mask, the winners' slots and valid bits).  After
the loop, every winner's payload (sid, ts, value bits), subscriber row
and active flag are gathered at once by masked lane sums, the fan-out
work items are formed by a one-hot expansion, co-inputs are fetched,
the reduced-branch VM runs as a vectorized select tree, and the
Listing-2 window/consistency verdict is computed — all before anything
is written back to HBM.  The staged round lowers the same dataflow as
five XLA ops with an HBM round-trip between each.

Gather idiom: a row fetch is a one-hot matmul on the MXU, or, where
the table is laid out as rows (the queue's payload planes, the
subscriber table), a masked lane sum in int32.  A one-hot f32 matmul is
exact only for values a float32 represents exactly, so int32 planes
(and float payloads, which ride as their bits) are gathered as split
16-bit halves — ``hi = x >> 16`` and ``lo = x & 0xffff`` both fit f32's
24-bit mantissa — and recombined (the ``stream_dispatch`` timestamp
trick, generalized).  Both are exact at any bit pattern, sign of zero
and NaN payloads included.

The MXU passes ask for ``Precision.HIGHEST``: a default-precision f32
matmul rounds its operands toward bf16 (8 mantissa bits), which does
not carry a 16-bit half exactly — on a v5e chip the fused round lost
sink records without it.

VMEM sizing: the dominant intermediates are the (W, N') one-hot gather
operands and the (W, R) register file of stages 2-3, W = batch*max_out
work lanes, N' = n_streams padded to 128, R = n_regs; narrow (N', 1..16)
table columns also occupy whole 128-lane tiles.  The winners' gathers
take (batch, ``_LANE_CHUNK``) masks, a loop step at a time.  At the
1,024-tenant IoT deployment (Q = 8,192, W = 512, N' = 3,712: the shapes
of ``tests/test_tpu_compile.py``) the v5e compiler asks for 93.18 MiB
of scoped VMEM for the fused kernel (93.47 MiB for the apply kernel)
against Mosaic's default 16 MiB, so every kernel here raises the limit
to ``_VMEM_LIMIT`` (v5e has 128 MiB per core).  That deployment then
runs on the chip, with about 7 MiB to spare: a larger W * N' does not
fit and should keep ``fused_round`` off.  The ``HIGHEST`` gathers take
most of it: with default-precision dots the apply kernel asks for
21.52 MiB, and the winners' (batch, N') gathers as ``HIGHEST`` matmuls
in place of masked sums took the fused kernel's ask to 105.58 MiB.
(An ask is the size the compiler reports when it refuses a smaller
limit.)  Code size and compile time grow with W * N' too (~30 MB of kernel
code at that size; the fused superstep compiles in 70-80 s on the
chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import program as pvm
from repro.kernels.round_fuse.ref import (
    FUSABLE_OPS, INT_MAX, INT_MIN, RegLayout)
from repro.kernels.sched_pop.ref import FAIR_SCALE, RANK_LIM

_EPS = pvm._EPS

# Scoped-VMEM ceiling for the kernels below.  Mosaic's default scope
# (16 MiB on v5e) is too small for the one-hot gather operands at
# deployment stream counts; v5e has 128 MiB of VMEM per core.
_VMEM_LIMIT = 100 * 2 ** 20
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
# Lanes per loop step of ``_lane_gather``.
_LANE_CHUNK = 512


# --------------------------------------------------------------------------
# exact one-hot gathers
# --------------------------------------------------------------------------

def _onehot(idx_col: jnp.ndarray, n: int) -> jnp.ndarray:
    """(W, 1) int32 indices -> (W, n) f32 one-hot rows."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (idx_col.shape[0], n), 1)
    return (lanes == idx_col).astype(jnp.float32)


def _gather_i32(onehot: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Exact int32 row gather as two 16-bit-half MXU matmuls.
    onehot: (W, n) f32; table: (n, X) int32 -> (W, X) int32."""
    hi = jnp.dot(onehot, (table >> 16).astype(jnp.float32),
                 precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    lo = jnp.dot(onehot, (table & 0xFFFF).astype(jnp.float32),
                 precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    return (hi.astype(jnp.int32) << 16) | lo.astype(jnp.int32)


def _gather_f32(onehot: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Exact float32 row gather: floats ride as their bits."""
    bits = _gather_i32(onehot, jax.lax.bitcast_convert_type(table, jnp.int32))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _to_col(row: jnp.ndarray) -> jnp.ndarray:
    """(1, n) int32 row -> (n, 1) column, as a masked lane sum over the
    diagonal of its broadcast (exact: one term per row)."""
    n = row.shape[1]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(diag, row, 0), axis=1, keepdims=True)


def _lane_gather(idx_col: jnp.ndarray, rows_ref) -> list:
    """Exact int32 gather from a table laid out as rows: entry k of the
    result is the (W, 1) column ``rows_ref[k, idx_col]`` (0 where an
    index is out of range).  Masked lane sums, ``_LANE_CHUNK`` lanes a
    loop step, so the code and the (W, chunk) temporaries stay small."""
    W = idx_col.shape[0]
    X, n = rows_ref.shape

    def part(lo, size, acc):
        hit = (jax.lax.broadcasted_iota(jnp.int32, (W, size), 1) + lo
               == idx_col)
        def row(k):
            return jnp.where(hit, rows_ref[k:k + 1, pl.ds(lo, size)], 0)
        return [a + jnp.sum(row(k), axis=1, keepdims=True)
                for k, a in enumerate(acc)]

    acc = [jnp.zeros((W, 1), jnp.int32)] * X
    n_full, rem = divmod(n, _LANE_CHUNK)
    if n_full:
        acc = jax.lax.fori_loop(0, n_full, lambda c, acc: part(
            pl.multiple_of(c * _LANE_CHUNK, _LANE_CHUNK), _LANE_CHUNK, acc),
            acc)
    return part(n - rem, rem, acc) if rem else acc


def _lane_f32(mask: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Extract one float32 lane per row by masked sum *in bit space*
    ((W, n) mask/values -> (W, 1)) — a float-space sum would already
    lose ``-0.0 + 0.0 = +0.0``."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    v = jnp.sum(jnp.where(mask, bits, 0), axis=1, keepdims=True)
    return jax.lax.bitcast_convert_type(v, jnp.float32)


# --------------------------------------------------------------------------
# stages 2 + 3 as kernel-internal values (shared by both kernels)
# --------------------------------------------------------------------------

def _bool(x):
    return (x != 0.0).astype(jnp.float32)


def _safe_div(x, y):
    tiny = jnp.abs(y) < _EPS
    return jnp.where(tiny, 0.0, x / jnp.where(tiny, 1.0, y))


# (opcode, value_fn(av, bv, dv, ca)) for every fusable op except NOP,
# which is the select chain's default.  Each fn mirrors the
# ``pvm._BRANCHES`` entry on (W, 1) lanes.
_VM_CASES = (
    (pvm.OP_MOV, lambda av, bv, dv, ca: av),
    (pvm.OP_CONST, lambda av, bv, dv, ca: ca),
    (pvm.OP_ADD, lambda av, bv, dv, ca: av + bv),
    (pvm.OP_SUB, lambda av, bv, dv, ca: av - bv),
    (pvm.OP_MUL, lambda av, bv, dv, ca: av * bv),
    (pvm.OP_DIV, lambda av, bv, dv, ca: _safe_div(av, bv)),
    (pvm.OP_MIN, lambda av, bv, dv, ca: jnp.minimum(av, bv)),
    (pvm.OP_MAX, lambda av, bv, dv, ca: jnp.maximum(av, bv)),
    (pvm.OP_NEG, lambda av, bv, dv, ca: -av),
    (pvm.OP_ABS, lambda av, bv, dv, ca: jnp.abs(av)),
    (pvm.OP_SQRT, lambda av, bv, dv, ca: jnp.sqrt(jnp.maximum(av, 0.0))),
    (pvm.OP_FLOOR, lambda av, bv, dv, ca: jnp.floor(av)),
    (pvm.OP_LT, lambda av, bv, dv, ca: (av < bv).astype(jnp.float32)),
    (pvm.OP_LE, lambda av, bv, dv, ca: (av <= bv).astype(jnp.float32)),
    (pvm.OP_EQ, lambda av, bv, dv, ca: (av == bv).astype(jnp.float32)),
    (pvm.OP_NE, lambda av, bv, dv, ca: (av != bv).astype(jnp.float32)),
    (pvm.OP_AND, lambda av, bv, dv, ca: _bool(av) * _bool(bv)),
    (pvm.OP_OR, lambda av, bv, dv, ca: jnp.maximum(_bool(av), _bool(bv))),
    (pvm.OP_NOT, lambda av, bv, dv, ca: 1.0 - _bool(av)),
    (pvm.OP_SELECT, lambda av, bv, dv, ca: jnp.where(av != 0.0, bv, dv)),
    (pvm.OP_ROUND, lambda av, bv, dv, ca: jnp.round(av)),
    (pvm.OP_SIGN, lambda av, bv, dv, ca: jnp.sign(av)),
)
assert {op for op, _ in _VM_CASES} | {pvm.OP_NOP} == FUSABLE_OPS


def _apply_body(layout: RegLayout, n_rows: int, prog_len: int,
                in_tbl, progs_flat, consts_tbl, comp_col, act_col,
                values_tbl, ts_col,
                rows_col, tsid_col, src_col, wivals, wits_col, wivalid_col):
    """Stages 2+3 on kernel values: co-input fetch, reduced-branch VM,
    window gate.  Row tables are (N', X)-shaped VMEM values; per-work
    planes are (W, 1) columns / (W, C) payloads.  Returns ``(new_vals,
    ts_out, live, keep, keep_ts, passf, badf)`` — new_vals (W, C) f32,
    the rest (W, 1) int32/bool."""
    W = rows_col.shape[0]
    M, C, R = layout.max_in, layout.channels, layout.n_regs
    n_pad = in_tbl.shape[0]

    oh_rows = _onehot(rows_col, n_pad)
    in_row = _gather_i32(oh_rows, in_tbl)                  # (W, M)
    in_valid = in_row >= 0
    src_safe = jnp.clip(in_row, 0, n_rows - 1)

    # trigger slot: first co-input matching the work item's source
    # (argmax-of-bool semantics: 0 when none matches)
    m_iota = jax.lax.broadcasted_iota(jnp.int32, (W, M), 1)
    match = (in_row == src_col) & in_valid
    trig = jnp.min(jnp.where(match, m_iota, M), axis=1, keepdims=True)
    trig = jnp.where(trig == M, 0, trig)

    # per-slot co-input fetch; the trigger slot is overridden by the
    # fresh SU before validity masking, exactly like the staged gather
    flat_parts = []
    ts_run = jnp.full((W, 1), INT_MIN, jnp.int32)
    for m in range(M):
        oh_m = _onehot(src_safe[:, m:m + 1], n_pad)
        vals_m = _gather_f32(oh_m, values_tbl)             # (W, C)
        ts_m = _gather_i32(oh_m, ts_col)                   # (W, 1)
        valid_m = in_valid[:, m:m + 1]
        is_trig = trig == m
        vals_m = jnp.where(is_trig, wivals, vals_m)
        ts_m = jnp.where(is_trig, wits_col,
                         jnp.where(valid_m, ts_m, INT_MIN))
        flat_parts.append(jnp.where(valid_m, vals_m, 0.0))
        ts_run = jnp.maximum(ts_run, jnp.where(valid_m, ts_m, INT_MIN))
    flat_in = jnp.concatenate(flat_parts, axis=1)          # (W, M*C)

    prev_vals = _gather_f32(_onehot(tsid_col, n_pad), values_tbl)
    prev_ts = _gather_i32(_onehot(tsid_col, n_pad), ts_col)

    # register file by segment concatenation (the layout is contiguous:
    # inputs | prev | ts | trigger | result+filters+temps, all zero)
    regs = jnp.concatenate([
        flat_in, prev_vals,
        wits_col.astype(jnp.float32), trig.astype(jnp.float32),
        jnp.zeros((W, R - layout.reg_result), jnp.float32),
    ], axis=1)

    progs_rows = _gather_i32(oh_rows, progs_flat)          # (W, 4L)
    consts_rows = _gather_f32(oh_rows, consts_tbl)         # (W, K)
    l_iota = jax.lax.broadcasted_iota(jnp.int32, (W, 4 * prog_len), 1)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (W, R), 1)
    k_iota = jax.lax.broadcasted_iota(
        jnp.int32, (W, consts_rows.shape[1]), 1)

    def vm_step(i, regs):
        def col(j):
            return jnp.sum(jnp.where(l_iota == 4 * i + j, progs_rows, 0),
                           axis=1, keepdims=True)
        op, dst, a, b = col(0), col(1), col(2), col(3)
        # reads clamp over-range operands like XLA's gather; writes with
        # an over-range dst find no lane, like XLA's scatter-drop.
        # (Negative operands would *wrap* in XLA — fusable_rows keeps
        # such bytecode on the staged path.)
        a_r = jnp.minimum(a, R - 1)
        b_r = jnp.minimum(b, R - 1)
        d_r = jnp.minimum(dst, R - 1)
        av = _lane_f32(r_iota == a_r, regs)
        bv = _lane_f32(r_iota == b_r, regs)
        dv = _lane_f32(r_iota == d_r, regs)
        ca = _lane_f32(k_iota == jnp.minimum(a, consts_rows.shape[1] - 1),
                       consts_rows)
        val = dv                                           # NOP default
        for code, fn in _VM_CASES:
            val = jnp.where(op == code, fn(av, bv, dv, ca), val)
        return jnp.where(r_iota == dst, val, regs)

    regs = jax.lax.fori_loop(0, prog_len, vm_step, regs)

    new_vals = regs[:, layout.reg_result:layout.reg_result + C]
    finite = jnp.isfinite(new_vals)
    badf = jnp.any(~finite, axis=1, keepdims=True)
    new_vals = jnp.where(finite, new_vals, 0.0)
    passf = (regs[:, layout.reg_pref:layout.reg_pref + 1] != 0.0) \
        & (regs[:, layout.reg_postf:layout.reg_postf + 1] != 0.0)

    keep_ts = wits_col > prev_ts
    ts_out = jnp.maximum(jnp.maximum(wits_col, prev_ts), ts_run)
    comp = _gather_i32(_onehot(rows_col, n_pad), comp_col) != 0
    act = _gather_i32(_onehot(rows_col, n_pad), act_col) != 0
    live = wivalid_col & comp & act
    keep = live & keep_ts & passf
    return new_vals, ts_out, live, keep, keep_ts, passf, badf


def _pack_apply_outputs(outs, refs):
    new_vals, ts_out, live, keep, keep_ts, passf, badf = outs
    nv_ref, tso_ref, live_ref, keep_ref, kts_ref, pf_ref, bad_ref = refs
    nv_ref[:] = new_vals
    tso_ref[:] = ts_out
    live_ref[:] = live.astype(jnp.int32)
    keep_ref[:] = keep.astype(jnp.int32)
    kts_ref[:] = keep_ts.astype(jnp.int32)
    pf_ref[:] = passf.astype(jnp.int32)
    bad_ref[:] = badf.astype(jnp.int32)


# --------------------------------------------------------------------------
# the fused round megakernel (single-device stages 1-3)
# --------------------------------------------------------------------------

def _fused_round_kernel(prio_ref, seq_ref, valid_ref, qlive_ref, tenant_ref,
                        w_ref, pay_ref, sub_ref,
                        in_tbl_ref, progs_ref, consts_ref,
                        comp_ref, act_ref, values_ref, tstamp_ref,
                        take_ref, esid_ref, ets_ref, epop_ref, eact_ref,
                        evals_ref, wit_ref,
                        nv_ref, tso_ref, live_ref, keep_ref, kts_ref,
                        pf_ref, bad_ref,
                        *, batch: int, layout: RegLayout, n_rows: int,
                        prog_len: int):
    Q = prio_ref.shape[1]
    F = sub_ref.shape[0] - 1
    W = batch * F
    n_pad = sub_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, batch), 1)
    valid = valid_ref[:] != 0
    seq = seq_ref[:]
    tenant = tenant_ref[:]
    w = w_ref[:]
    key0 = jnp.where(valid, prio_ref[:], INT_MAX)
    tag0 = jnp.where(qlive_ref[:] != 0, 0, INT_MAX)

    # ---- stage 1a: selection pop (the sched_pop loop), carrying only
    # what decides the next winner ------------------------------------
    def step(b, carry):
        k1, tag, taken, take, pop = carry
        m1 = jnp.min(k1)
        c1 = k1 == m1
        m2 = jnp.min(jnp.where(c1, tag, INT_MAX))
        c2 = c1 & (tag == m2)
        m3 = jnp.min(jnp.where(c2, seq, INT_MAX))
        c3 = c2 & (seq == m3)
        i = jnp.min(jnp.where(c3, iota, Q))                # first on ties
        onehot = iota == i
        was_valid = jnp.any(onehot & valid)
        t_i = jnp.sum(jnp.where(onehot, tenant, 0))
        w_i = jnp.sum(jnp.where(onehot, w, 0))
        cnt = jnp.sum(jnp.where((taken != 0) & valid & (tenant == t_i),
                                1, 0)) + was_valid.astype(jnp.int32)
        rank = jnp.minimum(cnt, RANK_LIM)
        tagval = jnp.where(w_i > 0,
                           rank * FAIR_SCALE // jnp.maximum(w_i, 1), 0)
        bump = was_valid & (tenant == t_i) & valid & (w_i > 0) & (taken == 0)
        tag = jnp.where(bump, tagval, tag)
        tag = jnp.where(onehot, INT_MAX, tag)
        k1 = jnp.where(onehot, INT_MAX, k1)
        taken = jnp.where(onehot, 1, taken)
        col = iota_b == b
        take = jnp.where(col, i, take)
        pop = jnp.where(col, was_valid.astype(jnp.int32), pop)
        return k1, tag, taken, take, pop

    zero_b = jnp.zeros((1, batch), jnp.int32)
    _, _, _, take, pop = jax.lax.fori_loop(
        0, batch, step,
        (key0, tag0, jnp.zeros((1, Q), jnp.int32), zero_b, zero_b))

    # ---- stage 1b: every winner's payload (sid, ts, value bits),
    # subscriber row (out_table's F columns) and active flag, once ------
    pay = _lane_gather(_to_col(take), pay_ref)
    e_sid, e_ts = pay[0], pay[1]
    e_vbits = jnp.concatenate(pay[2:], axis=1)             # (batch, C)
    sub = _lane_gather(jnp.clip(e_sid, 0, n_rows - 1), sub_ref)
    e_act = sub[F] != 0
    e_valid = (_to_col(pop) != 0) & e_act
    trow = jnp.concatenate(sub[:F], axis=1)                # (batch, F)
    wi_t = jnp.where(e_valid & (trow >= 0), trow, -1)
    # work items: rows b*F .. b*F+F-1 carry winner b (one-hot expansion:
    # no (B, F) -> (W, 1) reshape)
    w_row = jax.lax.broadcasted_iota(jnp.int32, (W, batch), 0)
    b_first = jax.lax.broadcasted_iota(jnp.int32, (W, batch), 1) * F
    wi = _gather_i32(((w_row >= b_first) & (w_row < b_first + F))
                     .astype(jnp.float32),
                     jnp.concatenate(pay, axis=1))         # (W, 2 + C)
    wit_col = sum(_gather_i32((w_row == b_first + f).astype(jnp.float32),
                              wi_t[:, f:f + 1]) for f in range(F))

    take_ref[:] = take
    esid_ref[:] = e_sid
    ets_ref[:] = e_ts
    epop_ref[:] = pop
    eact_ref[:] = e_act.astype(jnp.int32)
    evals_ref[:] = jax.lax.bitcast_convert_type(e_vbits, jnp.float32)
    wit_ref[:] = wi_t

    # ---- stages 2 + 3 in the same kernel: winners never left VMEM ------
    rows_col = jnp.clip(wit_col, 0, n_rows - 1)
    _pack_apply_outputs(
        _apply_body(layout, n_rows, prog_len,
                    in_tbl_ref[:], progs_ref[:], consts_ref[:],
                    comp_ref[:], act_ref[:], values_ref[:], tstamp_ref[:],
                    rows_col, rows_col, wi[:, 0:1],
                    jax.lax.bitcast_convert_type(wi[:, 2:], jnp.float32),
                    wi[:, 1:2], wit_col >= 0),
        (nv_ref, tso_ref, live_ref, keep_ref, kts_ref, pf_ref, bad_ref))


def fused_round_call(prio_slot, seq, valid, t_slot, w_slot, sid, vals, ts,
                     batch: int, out_table, in_table, progs, consts,
                     is_composite, active, values, timestamps,
                     layout: RegLayout, *, interpret: bool = False):
    """Run the fused round megakernel.  Per-slot planes as in
    ``sched_pop_call``; per-row tables are the engine's (N, ...)
    DeviceTables leaves; ``layout`` pins the VM register file.  Returns
    ``(take, (e_sid, e_vals, e_ts, e_pop, e_act), wi_t, (new_vals,
    ts_out, live, keep, keep_ts, passf, badf))`` — bit-identical to the
    ``ref.py`` composition."""
    Q, C = vals.shape
    N, F = out_table.shape
    L = progs.shape[1]
    W = batch * F
    Qp = -(-Q // 128) * 128
    Np = -(-N // 128) * 128

    # the register-file segments the kernel concatenates must be
    # contiguous in the engine's layout
    assert layout.reg_inputs == 0
    assert layout.reg_prev == layout.max_in * layout.channels
    assert layout.reg_ts == layout.reg_prev + layout.channels
    assert layout.reg_trigger == layout.reg_ts + 1
    assert layout.reg_result == layout.reg_trigger + 1

    def qrow(x, fill=0):
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, (0, Qp - Q), constant_values=fill).reshape(1, Qp)

    def ncol(x):
        return jnp.pad(jnp.asarray(x, jnp.int32),
                       (0, Np - N)).reshape(Np, 1)

    def ntbl(x, dtype):
        x = jnp.asarray(x, dtype)
        return jnp.pad(x, ((0, Np - N),) + ((0, 0),) * (x.ndim - 1))

    qlive = qrow(jnp.ones((Q,), jnp.int32))
    i32b = jnp.int32
    # tables the winners are gathered from, laid out as rows: the queue's
    # sid, ts and value bits by channel; out_table's F columns and active
    pay = jnp.concatenate(
        [jnp.asarray(sid, i32b)[None], jnp.asarray(ts, i32b)[None],
         jax.lax.bitcast_convert_type(vals.astype(jnp.float32), i32b).T])
    sub = ntbl(jnp.concatenate([jnp.asarray(out_table, i32b),
                                jnp.asarray(active, i32b)[:, None]], axis=1),
               i32b).T
    outs = pl.pallas_call(
        functools.partial(_fused_round_kernel, batch=batch, layout=layout,
                          n_rows=N, prog_len=L),
        out_shape=(
            jax.ShapeDtypeStruct((1, batch), i32b),       # take
            jax.ShapeDtypeStruct((batch, 1), i32b),       # e_sid
            jax.ShapeDtypeStruct((batch, 1), i32b),       # e_ts
            jax.ShapeDtypeStruct((1, batch), i32b),       # e_pop
            jax.ShapeDtypeStruct((batch, 1), i32b),       # e_act
            jax.ShapeDtypeStruct((batch, C), jnp.float32),  # e_vals
            jax.ShapeDtypeStruct((batch, F), i32b),       # wi_t
            jax.ShapeDtypeStruct((W, C), jnp.float32),    # new_vals
            jax.ShapeDtypeStruct((W, 1), i32b),           # ts_out
            jax.ShapeDtypeStruct((W, 1), i32b),           # live
            jax.ShapeDtypeStruct((W, 1), i32b),           # keep
            jax.ShapeDtypeStruct((W, 1), i32b),           # keep_ts
            jax.ShapeDtypeStruct((W, 1), i32b),           # passf
            jax.ShapeDtypeStruct((W, 1), i32b),           # badf
        ),
        compiler_params=_PARAMS,
        name="fused_round",
        interpret=interpret,
    )(qrow(prio_slot), qrow(seq), qrow(valid), qlive, qrow(t_slot),
      qrow(w_slot), jnp.pad(pay, ((0, 0), (0, Qp - Q))), sub,
      ntbl(in_table, i32b),
      ntbl(progs, i32b).reshape(Np, L * 4),
      ntbl(consts, jnp.float32),
      ncol(is_composite), ncol(active),
      ntbl(values, jnp.float32), ncol(timestamps))
    (take, psid, pts, ppop, pact, pvals, wi_t,
     new_vals, ts_out, live, keep, keep_ts, passf, badf) = outs
    flat = lambda x: x.reshape(-1)
    return (take.reshape(batch),
            (flat(psid), pvals, flat(pts), flat(ppop) != 0, flat(pact) != 0),
            wi_t.reshape(W),
            (new_vals, flat(ts_out), flat(live) != 0, flat(keep) != 0,
             flat(keep_ts) != 0, flat(passf) != 0, flat(badf) != 0))


# --------------------------------------------------------------------------
# standalone stages 2+3 (the sharded round's post-exchange apply)
# --------------------------------------------------------------------------

def _apply_programs_kernel(wit_ref, tsid_ref, src_ref, wivals_ref, wits_ref,
                           wivalid_ref,
                           in_tbl_ref, progs_ref, consts_ref, comp_ref,
                           act_ref, values_ref, tstamp_ref,
                           nv_ref, tso_ref, live_ref, keep_ref, kts_ref,
                           pf_ref, bad_ref,
                           *, layout: RegLayout, n_rows: int, prog_len: int):
    _pack_apply_outputs(
        _apply_body(layout, n_rows, prog_len,
                    in_tbl_ref[:], progs_ref[:], consts_ref[:],
                    comp_ref[:], act_ref[:], values_ref[:], tstamp_ref[:],
                    wit_ref[:], tsid_ref[:], src_ref[:], wivals_ref[:],
                    wits_ref[:], wivalid_ref[:] != 0),
        (nv_ref, tso_ref, live_ref, keep_ref, kts_ref, pf_ref, bad_ref))


def apply_programs_call(layout: RegLayout, in_table, progs, consts,
                        is_composite, active, rows, t_sid, wi_src, wi_vals,
                        wi_ts, wi_valid, values_by_sid, timestamps_by_sid,
                        *, interpret: bool = False):
    """Stages 2+3 alone (the sharded round applies them after the
    exchange).  ``rows`` index the (N, ...) tables, ``t_sid`` the
    (n_sid, ...) value/timestamp snapshot — both pre-clipped like
    ``engine.process_work_items``.  Returns ``(new_vals, ts_out, live,
    keep, keep_ts, passf, badf)``, bit-identical to
    ``ref.apply_programs_ref``."""
    W = rows.shape[0]
    N = in_table.shape[0]
    n_sid = timestamps_by_sid.shape[0]
    L = progs.shape[1]
    assert N == n_sid, "kernel apply assumes one row space"
    Np = -(-N // 128) * 128

    def ntbl(x, dtype):
        x = jnp.asarray(x, dtype)
        return jnp.pad(x, ((0, Np - N),) + ((0, 0),) * (x.ndim - 1))

    def wcol(x):
        return jnp.asarray(x, jnp.int32).reshape(W, 1)

    i32b = jnp.int32
    outs = pl.pallas_call(
        functools.partial(_apply_programs_kernel, layout=layout, n_rows=N,
                          prog_len=L),
        out_shape=(
            jax.ShapeDtypeStruct((W, layout.channels), jnp.float32),
            jax.ShapeDtypeStruct((W, 1), i32b),           # ts_out
            jax.ShapeDtypeStruct((W, 1), i32b),           # live
            jax.ShapeDtypeStruct((W, 1), i32b),           # keep
            jax.ShapeDtypeStruct((W, 1), i32b),           # keep_ts
            jax.ShapeDtypeStruct((W, 1), i32b),           # passf
            jax.ShapeDtypeStruct((W, 1), i32b),           # badf
        ),
        compiler_params=_PARAMS,
        name="apply_programs",
        interpret=interpret,
    )(wcol(rows), wcol(t_sid), wcol(wi_src),
      jnp.asarray(wi_vals, jnp.float32), wcol(wi_ts), wcol(wi_valid),
      ntbl(in_table, i32b), ntbl(progs, i32b).reshape(Np, L * 4),
      ntbl(consts, jnp.float32),
      ntbl(jnp.asarray(is_composite, i32b).reshape(N, 1), i32b),
      ntbl(jnp.asarray(active, i32b).reshape(N, 1), i32b),
      ntbl(values_by_sid, jnp.float32),
      ntbl(jnp.asarray(timestamps_by_sid, i32b).reshape(N, 1), i32b))
    new_vals, ts_out, live, keep, keep_ts, passf, badf = outs
    flat = lambda x: x.reshape(-1)
    return (new_vals, flat(ts_out), flat(live) != 0, flat(keep) != 0,
            flat(keep_ts) != 0, flat(passf) != 0, flat(badf) != 0)


# --------------------------------------------------------------------------
# sharded exchange compaction
# --------------------------------------------------------------------------

def _exchange_compact_kernel(wit_ref, src_ref, wits_ref, wiits_ref,
                             wivals_ref, dest_ref,
                             xi_ref, xf_ref, drop_ref,
                             *, n_shards: int, slots: int):
    W = wit_ref.shape[1]
    DE = n_shards * slots
    dest = dest_ref[:]                                     # (1, W)
    routed = dest < n_shards
    d_iota = jax.lax.broadcasted_iota(jnp.int32, (n_shards, W), 0)
    onehot = routed & (d_iota == dest)                     # (D, W)
    # inclusive running count along the lanes as a matmul with the
    # upper-triangular ones matrix (Mosaic has no cumsum); 0/1 operands
    # and counts <= W are exact in f32
    w_row = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    w_col = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    cum = jnp.dot(onehot.astype(jnp.float32),
                  (w_row <= w_col).astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32).astype(jnp.int32) - 1
    rank = jnp.sum(jnp.where(onehot, cum, 0), axis=0, keepdims=True)
    fits = routed & (rank < slots)
    slot = jnp.where(fits, dest * slots + rank, DE)        # (1, W)
    s_iota = jax.lax.broadcasted_iota(jnp.int32, (DE, W), 0)
    oh_out = s_iota == slot                                # (DE, W)

    def scatter_i32(plane, default):
        # empty slots must read `default`: sum (x - default) then shift
        return jnp.sum(jnp.where(oh_out, plane - default, 0),
                       axis=1, keepdims=True) + default

    xi_ref[:] = jnp.concatenate(
        [scatter_i32(wit_ref[:], -1), scatter_i32(src_ref[:], -1),
         scatter_i32(wits_ref[:], -1),
         scatter_i32(wiits_ref[:], -1)], axis=1)           # (DE, 4)
    xf_ref[:] = _gather_f32(oh_out.astype(jnp.float32), wivals_ref[:])
    drop_ref[:] = (routed & ~fits).astype(jnp.int32)


def exchange_compact_call(wi_t, wi_src, wi_ts, wi_its, wi_vals, dest_shard,
                          n_shards: int, slots: int, *,
                          interpret: bool = False):
    """Kernelized ranked-scatter compaction: (W,) work items into
    (n_shards, slots) per-destination exchange buckets, array order
    preserved per destination.  Bit-identical to
    ``ref.exchange_compact_ref``."""
    W = wi_t.shape[0]
    C = wi_vals.shape[1]
    Wp = -(-W // 128) * 128
    DE = n_shards * slots

    def wrow(x, fill=0):
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, (0, Wp - W), constant_values=fill).reshape(1, Wp)

    xi, xf, drop = pl.pallas_call(
        functools.partial(_exchange_compact_kernel, n_shards=n_shards,
                          slots=slots),
        out_shape=(
            jax.ShapeDtypeStruct((DE, 4), jnp.int32),
            jax.ShapeDtypeStruct((DE, C), jnp.float32),
            jax.ShapeDtypeStruct((1, Wp), jnp.int32),
        ),
        compiler_params=_PARAMS,
        name="exchange_compact",
        interpret=interpret,
    )(wrow(wi_t), wrow(wi_src), wrow(wi_ts), wrow(wi_its),
      jnp.pad(jnp.asarray(wi_vals, jnp.float32), ((0, Wp - W), (0, 0))),
      wrow(dest_shard, fill=n_shards))   # pad lanes are unrouted
    return (xi.reshape(n_shards, slots, 4),
            xf.reshape(n_shards, slots, C),
            drop.reshape(Wp)[:W] != 0)
