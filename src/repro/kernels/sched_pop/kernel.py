"""Pallas TPU kernel: fused scheduler pop — key build + top-B selection
+ winner gather in one VMEM-resident kernel.

GPU thinking for a priority queue is heap surgery; the classic XLA
answer is a full-queue multi-key sort.  The TPU-native reshaping: the
whole queue's key planes ((1, Q) int32 vectors — priority, virtual fair
tag, FIFO seq) live in VMEM/VREGs, and one winner per step falls out of
a vectorized lexicographic min-reduce over them.  ``batch`` steps of a
``fori_loop`` replace the O(Q log Q) sorts with O(Q·batch) VPU work,
the weighted-fair tag is maintained *incrementally* (only the winning
tenant's plane lanes are rewritten each step — the WFQ head property
makes that exact, see ``ref.py``), and the winners' payload rows are
gathered before anything leaves VMEM: every plane — float payloads
included, bitcast to int32 — by masked one-hot sums, exact at any bit
pattern (a float-space sum would already lose ``-0.0 + 0.0 = +0.0``).

Slot count is padded to the 128-lane boundary; pad lanes carry the
``(INT_MAX, INT_MAX)`` retired-slot key pair, which no live slot can
reach, so they are never selected while a real slot remains.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sched_pop.ref import FAIR_SCALE, INT_MAX, RANK_LIM


def _sched_pop_kernel(prio_ref, seq_ref, valid_ref, live_ref, tenant_ref,
                      w_ref, sid_ref, ts_ref, vals_ref,
                      take_ref, psid_ref, pts_ref, pvalid_ref, pvals_ref,
                      *, batch: int):
    Q = prio_ref.shape[1]
    C = vals_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, batch), 1)
    row_b = jax.lax.broadcasted_iota(jnp.int32, (batch, C), 0)
    iota_col = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    valid = valid_ref[:] != 0
    seq = seq_ref[:]
    tenant = tenant_ref[:]
    w = w_ref[:]
    sid = sid_ref[:]
    ts = ts_ref[:]
    # payload rows as raw bits: the masked sum below is then exact for
    # every float value, sign of zero included
    vals_bits = jax.lax.bitcast_convert_type(vals_ref[:], jnp.int32)
    key0 = jnp.where(valid, prio_ref[:], INT_MAX)
    # pad lanes start retired: both planes at INT_MAX, unreachable live
    tag0 = jnp.where(live_ref[:] != 0, 0, INT_MAX)

    def step(b, carry):
        k1, tag, taken, take, psid, pts, pvalid, pvals = carry
        m1 = jnp.min(k1)
        c1 = k1 == m1
        m2 = jnp.min(jnp.where(c1, tag, INT_MAX))
        c2 = c1 & (tag == m2)
        m3 = jnp.min(jnp.where(c2, seq, INT_MAX))
        c3 = c2 & (seq == m3)
        i = jnp.min(jnp.where(c3, iota, Q))            # first index on ties
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
        # fused winner gather: masked one-hot sums over int32 (exact at
        # any bit pattern; payload floats ride as their bits)
        col = iota_b == b
        take = jnp.where(col, i, take)
        psid = jnp.where(col, jnp.sum(jnp.where(onehot, sid, 0)), psid)
        pts = jnp.where(col, jnp.sum(jnp.where(onehot, ts, 0)), pts)
        pvalid = jnp.where(col, was_valid.astype(jnp.int32), pvalid)
        vals_i = jnp.sum(jnp.where(iota_col == i, vals_bits, 0),
                         axis=0, keepdims=True)        # (1, C) bits
        pvals = jnp.where(row_b == b, vals_i, pvals)
        return k1, tag, taken, take, psid, pts, pvalid, pvals

    zero_b = jnp.zeros((1, batch), jnp.int32)
    _, _, _, take, psid, pts, pvalid, pvals = jax.lax.fori_loop(
        0, batch, step,
        (key0, tag0, jnp.zeros((1, Q), jnp.int32),
         zero_b, zero_b, zero_b, zero_b,
         jnp.zeros((batch, C), jnp.int32)))
    take_ref[:] = take
    psid_ref[:] = psid
    pts_ref[:] = pts
    pvalid_ref[:] = pvalid
    pvals_ref[:] = jax.lax.bitcast_convert_type(pvals, jnp.float32)


def sched_pop_call(prio, seq, valid, tenant, w_slot, sid, vals, ts,
                   batch: int, *, interpret: bool = False):
    """Run the fused pop kernel.  All per-slot planes are (Q,) int32
    (``valid`` may be bool); ``vals`` is (Q, C) float32.  Returns
    ``(take, (p_sid, p_vals, p_ts, p_valid))`` with (batch,)-shaped
    outputs — bit-identical to ``ref.sched_pop_ref`` + jnp gathers."""
    Q, C = vals.shape
    Qp = -(-Q // 128) * 128
    pad = Qp - Q

    def i32row(x, fill=0):
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, (0, pad), constant_values=fill).reshape(1, Qp)

    live = i32row(jnp.ones((Q,), jnp.int32))
    outs = pl.pallas_call(
        functools.partial(_sched_pop_kernel, batch=batch),
        out_shape=(
            jax.ShapeDtypeStruct((1, batch), jnp.int32),   # take
            jax.ShapeDtypeStruct((1, batch), jnp.int32),   # p_sid
            jax.ShapeDtypeStruct((1, batch), jnp.int32),   # p_ts
            jax.ShapeDtypeStruct((1, batch), jnp.int32),   # p_valid
            jax.ShapeDtypeStruct((batch, C), jnp.float32), # p_vals
        ),
        name="sched_pop",
        interpret=interpret,
    )(i32row(prio), i32row(seq), i32row(valid), live, i32row(tenant),
      i32row(w_slot), i32row(sid), i32row(ts),
      jnp.pad(vals.astype(jnp.float32), ((0, pad), (0, 0))))
    take, psid, pts, pvalid, pvals = outs
    return take.reshape(batch), (psid.reshape(batch), pvals,
                                 pts.reshape(batch),
                                 pvalid.reshape(batch) != 0)
