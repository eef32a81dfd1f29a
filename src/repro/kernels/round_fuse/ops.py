"""Dispatch wrappers for the fused round.

Three entry points, each picking the Pallas kernel on TPU and the
pure-jnp refs everywhere else (the refs *are* the CPU fallback, so a
CPU round never pays Pallas interpret-mode overhead — the
``sched_pop`` convention):

* ``fused_stages``    — single-device stages 1-3 (engine ``make_step``
  with ``fused_round`` on).
* ``apply_programs``  — stages 2+3 alone (the sharded round, after the
  exchange).
* ``exchange_compact`` — the sharded exchange's ranked-scatter
  compaction.

All three are deliberately *not* jitted: they trace inline into the
engine round / superstep scan like the stages they replace.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.round_fuse.ref import (
    RegLayout, apply_programs_ref, exchange_compact_ref, pop_dispatch_ref)


def _pick(use_kernel: Optional[bool], interpret: Optional[bool]
          ) -> Tuple[bool, bool]:
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    return use_kernel, (not on_tpu) if interpret is None else interpret


def fused_stages(prio_slot, seq, valid, t_slot, w_slot, sid, vals, ts,
                 batch: int, out_table, in_table, progs, consts,
                 is_composite, active, values, timestamps,
                 layout: RegLayout, *, use_kernel: Optional[bool] = None,
                 interpret: Optional[bool] = None):
    """Stages 1-3 of the single-device round as one operation: packed
    top-``batch`` pop, fan-out, co-input fetch + reduced-branch VM, and
    the Listing-2 window gate.  Per-slot planes as in ``sched_pop``;
    the tables/state leaves are the engine's (N, ...) arrays.  Returns
    ``(take, (e_sid, e_vals, e_ts, e_pop, e_act), wi_t, (new_vals,
    ts_out, live, keep, keep_ts, passf, badf))`` — wi_t already masked
    to -1 for invalid/revoked lanes, so ``wi_t >= 0`` is the work-item
    validity mask."""
    use_kernel, interp = _pick(use_kernel, interpret)
    if use_kernel:
        from repro.kernels.round_fuse.kernel import fused_round_call
        return fused_round_call(prio_slot, seq, valid, t_slot, w_slot, sid,
                                vals, ts, batch, out_table, in_table, progs,
                                consts, is_composite, active, values,
                                timestamps, layout, interpret=interp)
    take, popped, (wi_t, wi_src, wi_vals, wi_ts) = pop_dispatch_ref(
        prio_slot, seq, valid, t_slot, w_slot, sid, vals, ts, batch,
        out_table, active)
    N = out_table.shape[0]
    rows = jnp.clip(wi_t, 0, N - 1)
    applied = apply_programs_ref(
        layout, in_table, progs, consts, is_composite, active,
        rows, rows, wi_src, wi_vals, wi_ts, wi_t >= 0, values, timestamps)
    return take, popped, wi_t, applied


def apply_programs(layout: RegLayout, in_table, progs, consts, is_composite,
                   active, rows, t_sid, wi_src, wi_vals, wi_ts, wi_valid,
                   values_by_sid, timestamps_by_sid, *,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None):
    """Stages 2+3 for a work-item batch (the sharded round's
    post-exchange apply) — ``engine.process_work_items`` semantics with
    the reduced-branch VM, returning the raw masks ``(new_vals, ts_out,
    live, keep, keep_ts, passf, badf)``.  The kernel path requires the
    tables and the value/timestamp snapshot to share one row space
    (``rows is t_sid`` up to clipping) and asserts it; a caller whose row
    spaces differ asks for the reference with ``use_kernel=False``."""
    use_kernel, interp = _pick(use_kernel, interpret)
    if use_kernel:
        from repro.kernels.round_fuse.kernel import apply_programs_call
        return apply_programs_call(layout, in_table, progs, consts,
                                   is_composite, active, rows, t_sid, wi_src,
                                   wi_vals, wi_ts, wi_valid, values_by_sid,
                                   timestamps_by_sid, interpret=interp)
    return apply_programs_ref(layout, in_table, progs, consts, is_composite,
                              active, rows, t_sid, wi_src, wi_vals, wi_ts,
                              wi_valid, values_by_sid, timestamps_by_sid)


def exchange_compact(wi_t, wi_src, wi_ts, wi_its, wi_vals, dest_shard,
                     n_shards: int, slots: int, *,
                     use_kernel: Optional[bool] = None,
                     interpret: Optional[bool] = None):
    """Rank-and-scatter (W,) work items into (n_shards, slots)
    fixed-size exchange buckets, array order preserved per destination;
    ``dest_shard == n_shards`` marks unrouted lanes.  Returns ``(xi,
    xf, x_drop)``: (D, E, 4) int32 ``(target, src, ts, its)`` -1-padded,
    (D, E, C) float32 payloads, and the (W,) overflow mask."""
    use_kernel, interp = _pick(use_kernel, interpret)
    if use_kernel:
        from repro.kernels.round_fuse.kernel import exchange_compact_call
        return exchange_compact_call(wi_t, wi_src, wi_ts, wi_its, wi_vals,
                                     dest_shard, n_shards, slots,
                                     interpret=interp)
    return exchange_compact_ref(wi_t, wi_src, wi_ts, wi_its, wi_vals,
                                dest_shard, n_shards, slots)
