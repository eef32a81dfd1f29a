"""Sharded stream engine: partition the pub/sub plane across devices.

The paper scales by distributing the processing topology across a STORM
cluster (§V); the single-device engine in :mod:`repro.core.engine` runs the
whole stream space on one XLA device.  This module partitions *streams*
across a 1-D ``jax.sharding.Mesh`` ("shards" axis): every shard owns a
contiguous sid block (or a tenant-hash bucket) and holds its own
:class:`EngineState` slice — values, timestamps, pending-SU queue, seq
counter and stats — while the four-stage round runs per shard under
``shard_map``.

Cross-shard subscriptions are served by a new **exchange stage** between
stage 1 (fan-out) and stage 2 (fetch): work items whose target stream lives
on another shard are compacted into fixed-size per-destination exchange
buffers and delivered with one ``all_to_all`` collective.  Buffer overflow
drops are counted in ``stats["dropped_overflow"]`` (never silent).  Co-input
fetches read an ``all_gather`` snapshot taken right after ingest — the same
snapshot the single-device engine reads — so the Listing-2 consistency
semantics (stale-discard, same-(sid, ts) coalescing) are preserved exactly.

Bit-exact equivalence with the single-device engine holds whenever no
exchange buffer overflows and each round drains every queue (batch ≥ queue
occupancy): both engines then process the same work-item set per round, and
intra-round coalescing ties break on the *content* key (trigger sid, see
``consistency.resolve_winners``) rather than batch layout.

The per-shard round:

    phase 0   ingest SUs routed to their owner shard (host-side routing)
    pop       per-shard priority pop from the local queue
    snapshot  all_gather values/timestamps -> by-sid global view
    stage 1   fan-out via the shard-local out-tables
    exchange  per-destination buffers + all_to_all   <- NEW
    stage 2   gather co-inputs from the snapshot
    stage 3   bytecode VM + Listing-2 filters
    stage 4   store into the owner shard's slice, re-enqueue locally

Live churn (PR 2): :class:`ShardedStreamEngine` extends the admission
plane across the mesh — newly admitted sids claim a spare physical slot
on the tenant-preferred or least-loaded shard (host bookkeeping plus one
replicated gmap edit; inactive rows are inert, so placement moves no
data), revocations release the slot, and :meth:`~ShardedStreamEngine.
rebalance` migrates whole rows (tables + state slice) off overfull
shards with :func:`repro.core.admission.migrate_row`.  All of it leaves
the compiled round untouched.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import admission
from repro.core.config import EngineConfig
from repro.core.engine import (DLQ_OVERFLOW, DLQ_POISONED, DLQ_REVOKED,
                               INT_MIN, STAT_KEYS,
                               DeviceTables, EngineState, IngestBatch,
                               IngestRing, SinkBatch, SinkSpool, StreamEngine,
                               _pop, _stage_ring, dlq_append,
                               fanout_reference, fault_events, fault_phase,
                               ingest_phase, process_work_items, scan_rounds,
                               store_and_emit, tenant_occupancy)
from repro.core.registry import EngineTables, Registry

AXIS = "shards"


# --------------------------------------------------------------------------
# partitioner
# --------------------------------------------------------------------------

class ShardPlan(NamedTuple):
    """Static placement of the stream space on the mesh."""
    n_shards: int
    n_local: int                  # padded per-shard stream capacity
    sid_to_shard: np.ndarray      # (N,) int32 — the global sid -> shard map
    sid_to_local: np.ndarray      # (N,) int32 row within the owner's slice
    sid_to_flat: np.ndarray       # (N,) int32 == shard * n_local + local
    local_to_sid: np.ndarray      # (n_shards, n_local) int32, -1 pad


def plan_partition(cfg: EngineConfig, tenant_of_sid: np.ndarray,
                   n_shards: Optional[int] = None,
                   partition: Optional[str] = None) -> ShardPlan:
    """Assign every sid to a shard: ``"block"`` gives contiguous sid ranges
    (cheap locality for pipelines built incrementally), ``"tenant"`` hashes
    the owning tenant so one tenant's pipeline stays co-located.

    The plan covers the full capacity: *every* sid — including spare rows
    no stream occupies yet — gets a ``(shard, local)`` slot, so the
    admission plane can later claim spare slots without resizing anything.
    ``n_local`` is the padded per-shard row count (``"tenant"`` pads to
    the largest bucket; the unmapped remainder rows are the "holes" the
    sharded engine hands to incoming placements first).  The maps are
    plain mutable numpy arrays: the sharded engine edits them in place as
    placements change, mirroring the replicated on-device ``GlobalMaps``."""
    N = cfg.n_streams
    n_shards = int(n_shards or cfg.n_shards)
    partition = partition or cfg.partition
    sids = np.arange(N)
    if partition == "block":
        n_local = -(-N // n_shards)
        sid_to_shard = sids // n_local
        sid_to_local = sids % n_local
    elif partition == "tenant":
        sid_to_shard = np.asarray(tenant_of_sid, np.int64) % n_shards
        counts = np.zeros(n_shards, np.int64)
        sid_to_local = np.zeros(N, np.int64)
        for sid in range(N):
            s = sid_to_shard[sid]
            sid_to_local[sid] = counts[s]
            counts[s] += 1
        n_local = max(int(counts.max(initial=1)), 1)
    else:
        raise ValueError(f"unknown partition {partition!r}")
    sid_to_flat = sid_to_shard * n_local + sid_to_local
    local_to_sid = np.full((n_shards, n_local), -1, np.int32)
    local_to_sid[sid_to_shard, sid_to_local] = sids
    return ShardPlan(n_shards, n_local,
                     sid_to_shard.astype(np.int32),
                     sid_to_local.astype(np.int32),
                     sid_to_flat.astype(np.int32), local_to_sid)


def shard_tables(tables: EngineTables, plan: ShardPlan) -> EngineTables:
    """Permute the global table rows into (n_shards, n_local, ...) slices.
    Pad rows are inert: no inputs, no subscribers, NOP programs, and
    ``active=False`` — indistinguishable from revoked rows, which is what
    lets live admission claim them as pure table edits."""
    S, L = plan.n_shards, plan.n_local

    def scatter(rows: np.ndarray, fill) -> np.ndarray:
        out = np.full((S, L) + rows.shape[1:], fill, rows.dtype)
        out[plan.sid_to_shard, plan.sid_to_local] = rows
        return out

    return EngineTables(
        in_table=scatter(tables.in_table, -1),
        in_count=scatter(tables.in_count, 0),
        out_table=scatter(tables.out_table, -1),
        out_count=scatter(tables.out_count, 0),
        progs=scatter(tables.progs, 0),
        consts=scatter(tables.consts, 0),
        is_composite=scatter(tables.is_composite, False),
        tenant=scatter(tables.tenant, 0),
        priority=scatter(tables.priority, 0),
        n_channels=scatter(tables.n_channels, 1),
        model_backed=scatter(tables.model_backed, False),
        active=scatter(tables.active, False),
        # per-tenant QoS tables ride replicated: every shard carries its
        # own (n_tenants,) copy, so fairness/quota hold per shard and the
        # admission ops' ``...``-indexed edits hit all copies at once
        weight=np.tile(tables.weight[None], (S, 1)),
        quota=np.tile(tables.quota[None], (S, 1)),
        burst=np.tile(tables.burst[None], (S, 1)),
        breaker=np.tile(tables.breaker[None], (S, 1)),
    )


class GlobalMaps(NamedTuple):
    """Small replicated lookup tables shared by every shard."""
    sid_to_shard: jnp.ndarray     # (N,)
    sid_to_local: jnp.ndarray     # (N,)
    sid_to_flat: jnp.ndarray      # (N,)
    priority: jnp.ndarray         # (N,) by global sid (queues hold sids)

    @classmethod
    def build(cls, priority: Optional[np.ndarray], plan: ShardPlan) -> "GlobalMaps":
        n = plan.sid_to_shard.shape[0]
        if priority is None:
            priority = np.zeros((n,), np.int32)
        return cls(
            sid_to_shard=jnp.asarray(plan.sid_to_shard),
            sid_to_local=jnp.asarray(plan.sid_to_local),
            sid_to_flat=jnp.asarray(plan.sid_to_flat),
            priority=jnp.asarray(priority, jnp.int32),
        )


@functools.partial(jax.jit, donate_argnums=(0,))
def _place_sid_op(gmap: GlobalMaps, sid, shard, local, n_local, priority
                  ) -> GlobalMaps:
    """Point one global sid at a (shard, local) slot in the replicated
    lookup maps — the gmap half of a live admission / migration (a pure
    table edit, like everything in :mod:`repro.core.admission`)."""
    return GlobalMaps(
        sid_to_shard=gmap.sid_to_shard.at[sid].set(shard),
        sid_to_local=gmap.sid_to_local.at[sid].set(local),
        sid_to_flat=gmap.sid_to_flat.at[sid].set(shard * n_local + local),
        priority=gmap.priority.at[sid].set(priority),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _stage_ring_op(ring: IngestRing, w_slot, w_sid, w_vals, w_ts, w_its,
                   rnd, pos, valid) -> IngestRing:
    """Per-shard :func:`repro.core.engine.stage_ring` vmapped over the
    leading shard axis: every shard's payload deltas are scattered into
    its resident ring slice and every slot's routing tag rewritten, in
    one dispatch (the inputs arrive pre-placed by one ``device_put``)."""
    return jax.vmap(_stage_ring)(ring, w_slot, w_sid, w_vals, w_ts, w_its,
                                 rnd, pos, valid)


def sharded_init_state(cfg: EngineConfig, plan: ShardPlan) -> EngineState:
    """Per-shard EngineState slices stacked on a leading shard axis."""
    S, L, C, Q = plan.n_shards, plan.n_local, cfg.channels, cfg.queue
    T = cfg.n_tenants
    Rr, D = cfg.retention_slots, cfg.dlq_slots
    return EngineState(
        values=jnp.zeros((S, L, C), jnp.float32),
        timestamps=jnp.full((S, L), INT_MIN, jnp.int32),
        q_sid=jnp.zeros((S, Q), jnp.int32),
        q_vals=jnp.zeros((S, Q, C), jnp.float32),
        q_ts=jnp.zeros((S, Q), jnp.int32),
        q_its=jnp.zeros((S, Q), jnp.int32),
        q_seq=jnp.zeros((S, Q), jnp.int32),
        q_valid=jnp.zeros((S, Q), bool),
        seq=jnp.zeros((S,), jnp.int32),
        tenant_emitted=jnp.zeros((S, T), jnp.int32),
        tokens=jnp.zeros((S, T), jnp.int32),
        tenant_queued=jnp.zeros((S, T), jnp.int32),
        tenant_dropped_quota=jnp.zeros((S, T), jnp.int32),
        tenant_dropped_overflow=jnp.zeros((S, T), jnp.int32),
        ret_vals=jnp.zeros((S, L, Rr, C), jnp.float32),
        ret_ts=jnp.zeros((S, L, Rr), jnp.int32),
        ret_its=jnp.zeros((S, L, Rr), jnp.int32),
        ret_count=jnp.zeros((S, L), jnp.int32),
        dlq_sid=jnp.zeros((S, D), jnp.int32),
        dlq_vals=jnp.zeros((S, D, C), jnp.float32),
        dlq_ts=jnp.zeros((S, D), jnp.int32),
        dlq_its=jnp.zeros((S, D), jnp.int32),
        dlq_reason=jnp.zeros((S, D), jnp.int32),
        dlq_tenant=jnp.zeros((S, D), jnp.int32),
        dlq_fill=jnp.zeros((S,), jnp.int32),
        quarantined=jnp.zeros((S, L), bool),
        fault_count=jnp.zeros((S, L), jnp.int32),
        fault_epoch=jnp.zeros((S, L), jnp.int32),
        fault_total=jnp.zeros((S, L), jnp.int32),
        round_idx=jnp.zeros((S,), jnp.int32),
        stats={k: jnp.zeros((S,), jnp.int32) for k in STAT_KEYS},
    )


# --------------------------------------------------------------------------
# elastic re-sharding
# --------------------------------------------------------------------------

_QOS_FIELDS = ("weight", "quota", "burst")
# replicated (per-shard copy) table planes: QoS plus the breaker config row
_REPL_FIELDS = _QOS_FIELDS + ("breaker",)


def reshard_snapshot(arrays, meta, n_shards: int,
                     partition: Optional[str] = None):
    """Re-lay a :meth:`StreamEngine.snapshot` out for a different shard
    count (or partition scheme) — the migration core of the elastic plane.
    Returns a new ``(arrays, meta)`` pair installable at ``n_shards``
    (``kind="sharded"`` for > 1, ``"single"`` for 1); the inputs are not
    mutated.  Both :meth:`StreamEngine.resize` and cross-shard-count
    :func:`~repro.core.engine.restore_engine` route through here, which is
    what makes restore the resize primitive's bit-exact oracle.

    Everything runs on host numpy at a superstep boundary:

    * per-stream table rows and per-sid state (values/timestamps/retention
      rings) are gathered into canonical by-sid order, then re-scattered
      through a fresh :func:`plan_partition`/:func:`shard_tables` layout —
      hole fills match inert/revoked rows exactly, so the round is
      bit-faithful;
    * pending-queue entries are drained shard-major in FIFO (``q_seq``)
      order and re-enqueued on each sid's new owner shard; entries beyond
      a shard's ``cfg.queue`` capacity on scale-in are counted
      (``dropped_overflow`` + ``purged`` + per-tenant) and dead-lettered,
      never silently lost;
    * dead letters re-spool on their sid's new owner (saturating at
      ``cfg.dlq_slots`` per shard, like any spool write);
    * per-tenant/stat totals are summed across the old shards and placed
      on shard 0 (readback sums shards, so counters are preserved);
      ``tenant_queued`` is recomputed from the migrated queues; ingest
      token buckets restart empty — bucket credit does not survive a
      resize (quotas refill on the next round).
    """
    cfg = EngineConfig(**meta["registry"]["cfg"])
    n_shards = int(n_shards)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    new_cfg = dataclasses.replace(
        cfg, n_shards=n_shards,
        partition=partition or cfg.partition).validate()
    N, C, Q, T = cfg.n_streams, cfg.channels, cfg.queue, cfg.n_tenants
    Rr, D = cfg.retention_slots, cfg.dlq_slots
    sharded_src = meta.get("kind") == "sharded"

    # ---- canonicalise the source into by-sid / flat host views ----------
    if sharded_src:
        old_flat = np.asarray(arrays["plan/sid_to_flat"], np.int64)

        def by_sid(x):
            # explicit leading dim: -1 is uninferrable for zero-size
            # arrays (e.g. retention buffers with retention_slots=0)
            x = np.asarray(x)
            return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])[old_flat]

        def qos(x):          # replicated per shard: any copy is canonical
            return np.asarray(x)[0]

        def lead(x):         # the single layout lacks the shard axis
            return np.asarray(x)

        def tot(x):          # totals live summed across shards
            x = np.asarray(x)
            return np.array(x.sum(axis=0), x.dtype)
    else:
        def by_sid(x):
            return np.asarray(x)

        qos = by_sid

        def lead(x):
            return np.asarray(x)[None]

        def tot(x):
            return np.array(x)   # copy: totals are mutated below

    def tab_leaf(f):
        src = arrays.get(f"tables/{f}")
        if src is None:     # snapshot predates the fault plane: cfg defaults
            return np.array([cfg.fault_window, cfg.fault_threshold,
                             cfg.fault_amp_ceiling], np.int32)
        return (qos if f in _REPL_FIELDS else by_sid)(src)

    tab = {f: tab_leaf(f) for f in DeviceTables._fields}
    tenant_flat = tab["tenant"].astype(np.int64)
    per_sid = {f: by_sid(arrays[f"state/{f}"])
               for f in ("values", "timestamps",
                         "ret_vals", "ret_ts", "ret_its", "ret_count")}
    # fault-plane per-stream leaves (absent in pre-fault-plane snapshots)
    for f, dt in (("quarantined", bool), ("fault_count", np.int32),
                  ("fault_epoch", np.int32), ("fault_total", np.int32)):
        src = arrays.get(f"state/{f}")
        per_sid[f] = by_sid(src) if src is not None \
            else np.zeros((N,), dt)
    r_idx = np.asarray(arrays.get("state/round_idx", 0))
    round_idx = np.int32(r_idx.max() if r_idx.ndim else r_idx)

    # queued SUs in canonical (shard-major, FIFO) order
    q_sid, q_vals = lead(arrays["state/q_sid"]), lead(arrays["state/q_vals"])
    q_ts, q_seq = lead(arrays["state/q_ts"]), lead(arrays["state/q_seq"])
    q_its = lead(arrays["state/q_its"])
    q_valid = lead(arrays["state/q_valid"])
    entries = []
    for s in range(q_sid.shape[0]):
        idx = np.nonzero(q_valid[s])[0]
        idx = idx[np.argsort(q_seq[s, idx], kind="stable")]
        entries.extend((int(q_sid[s, i]), np.array(q_vals[s, i]),
                        int(q_ts[s, i]), int(q_its[s, i])) for i in idx)

    # dead letters in drop (shard-major, spool) order
    d_sid, d_ts = lead(arrays["state/dlq_sid"]), lead(arrays["state/dlq_ts"])
    d_vals = lead(arrays["state/dlq_vals"])
    d_its = lead(arrays["state/dlq_its"])
    d_reason = lead(arrays["state/dlq_reason"])
    d_tenant = lead(arrays["state/dlq_tenant"])
    d_fill = np.atleast_1d(np.asarray(arrays["state/dlq_fill"]))
    letters = [(int(d_sid[s, i]), np.array(d_vals[s, i]), int(d_ts[s, i]),
                int(d_its[s, i]), int(d_reason[s, i]), int(d_tenant[s, i]))
               for s in range(d_sid.shape[0]) for i in range(int(d_fill[s]))]

    stat0 = np.zeros_like(np.asarray(arrays["state/stats/ingested"]))
    totals = {k: tot(arrays.get(f"state/stats/{k}", stat0))
              for k in STAT_KEYS}     # a key the snapshot predates reads 0
    t_emitted = tot(arrays["state/tenant_emitted"])
    t_drop_quota = tot(arrays["state/tenant_dropped_quota"])
    t_drop_over = tot(arrays["state/tenant_dropped_overflow"])

    # ---- rebuild at the target shard count ------------------------------
    plan = plan_partition(new_cfg, tenant_flat)
    sh_tab = shard_tables(EngineTables(**tab), plan)
    S2, L2 = plan.n_shards, plan.n_local
    F2 = S2 * L2

    values = np.zeros((F2, C), np.float32)
    timestamps = np.full((F2,), INT_MIN, np.int32)
    ret_vals = np.zeros((F2, Rr, C), np.float32)
    ret_ts = np.zeros((F2, Rr), np.int32)
    ret_its = np.zeros((F2, Rr), np.int32)
    ret_count = np.zeros((F2,), np.int32)
    values[plan.sid_to_flat] = per_sid["values"]
    timestamps[plan.sid_to_flat] = per_sid["timestamps"]
    ret_vals[plan.sid_to_flat] = per_sid["ret_vals"]
    ret_ts[plan.sid_to_flat] = per_sid["ret_ts"]
    ret_its[plan.sid_to_flat] = per_sid["ret_its"]
    ret_count[plan.sid_to_flat] = per_sid["ret_count"]
    quarantined = np.zeros((F2,), bool)
    f_count = np.zeros((F2,), np.int32)
    f_epoch = np.zeros((F2,), np.int32)
    f_total = np.zeros((F2,), np.int32)
    quarantined[plan.sid_to_flat] = per_sid["quarantined"]
    f_count[plan.sid_to_flat] = per_sid["fault_count"]
    f_epoch[plan.sid_to_flat] = per_sid["fault_epoch"]
    f_total[plan.sid_to_flat] = per_sid["fault_total"]

    nq_sid = np.zeros((S2, Q), np.int32)
    nq_vals = np.zeros((S2, Q, C), np.float32)
    nq_ts = np.zeros((S2, Q), np.int32)
    nq_its = np.zeros((S2, Q), np.int32)
    nq_seq = np.zeros((S2, Q), np.int32)
    nq_valid = np.zeros((S2, Q), bool)
    fill = np.zeros((S2,), np.int64)
    t_queued = np.zeros((S2, T), np.int32)
    for sid, vals, ts, its in entries:
        sid_c = min(max(sid, 0), N - 1)
        s = int(plan.sid_to_shard[sid_c])
        tn = min(max(int(tenant_flat[sid_c]), 0), T - 1)
        k = int(fill[s])
        if k < Q:
            nq_sid[s, k], nq_vals[s, k], nq_ts[s, k] = sid, vals, ts
            nq_its[s, k] = its
            nq_seq[s, k], nq_valid[s, k] = k, True
            fill[s] = k + 1
            t_queued[s, tn] += 1
        else:
            # scale-in squeezed more SUs onto this shard than its queue
            # holds: count + dead-letter, same contract as any overflow
            totals["dropped_overflow"] += 1
            totals["purged"] += 1
            t_drop_over[tn] += 1
            letters.append((sid, np.asarray(vals, np.float32), ts, its,
                            DLQ_OVERFLOW, tn))
    seq = fill.astype(np.int32)

    nd_sid = np.zeros((S2, D), np.int32)
    nd_vals = np.zeros((S2, D, C), np.float32)
    nd_ts = np.zeros((S2, D), np.int32)
    nd_its = np.zeros((S2, D), np.int32)
    nd_reason = np.zeros((S2, D), np.int32)
    nd_tenant = np.zeros((S2, D), np.int32)
    nd_fill = np.zeros((S2,), np.int32)
    if D > 0:
        for sid, vals, ts, its, reason, tn in letters:
            s = int(plan.sid_to_shard[min(max(sid, 0), N - 1)])
            k = int(nd_fill[s])
            if k < D:
                nd_sid[s, k], nd_vals[s, k], nd_ts[s, k] = sid, vals, ts
                nd_its[s, k] = its
                nd_reason[s, k], nd_tenant[s, k] = reason, tn
                nd_fill[s] = k + 1

    def place0(v):           # totals ride on shard 0; readback sums shards
        out = np.zeros((S2,) + v.shape, v.dtype)
        out[0] = v
        return out

    out = {f"tables/{f}": np.asarray(getattr(sh_tab, f))
           for f in DeviceTables._fields}
    out.update({
        "state/values": values.reshape(S2, L2, C),
        "state/timestamps": timestamps.reshape(S2, L2),
        "state/q_sid": nq_sid, "state/q_vals": nq_vals,
        "state/q_ts": nq_ts, "state/q_its": nq_its,
        "state/q_seq": nq_seq,
        "state/q_valid": nq_valid,
        "state/seq": seq,
        "state/tenant_emitted": place0(t_emitted),
        "state/tokens": np.zeros((S2, T), np.int32),
        "state/tenant_queued": t_queued,
        "state/tenant_dropped_quota": place0(t_drop_quota),
        "state/tenant_dropped_overflow": place0(t_drop_over),
        "state/ret_vals": ret_vals.reshape(S2, L2, Rr, C),
        "state/ret_ts": ret_ts.reshape(S2, L2, Rr),
        "state/ret_its": ret_its.reshape(S2, L2, Rr),
        "state/ret_count": ret_count.reshape(S2, L2),
        # every shard carries the same round counter (each increments once
        # per round), so migrated fault windows stay anchored correctly
        "state/quarantined": quarantined.reshape(S2, L2),
        "state/fault_count": f_count.reshape(S2, L2),
        "state/fault_epoch": f_epoch.reshape(S2, L2),
        "state/fault_total": f_total.reshape(S2, L2),
        "state/round_idx": np.full((S2,), round_idx, np.int32),
        "state/dlq_sid": nd_sid, "state/dlq_vals": nd_vals,
        "state/dlq_ts": nd_ts, "state/dlq_its": nd_its,
        "state/dlq_reason": nd_reason,
        "state/dlq_tenant": nd_tenant, "state/dlq_fill": nd_fill,
    })
    for k in STAT_KEYS:
        out[f"state/stats/{k}"] = place0(totals[k].reshape(()))
    if n_shards == 1:
        out = {k: v[0] for k, v in out.items()}
    else:
        out["gmap/sid_to_shard"] = plan.sid_to_shard.copy()
        out["gmap/sid_to_local"] = plan.sid_to_local.copy()
        out["gmap/sid_to_flat"] = plan.sid_to_flat.copy()
        out["gmap/priority"] = tab["priority"].astype(np.int32)
        out["plan/sid_to_shard"] = plan.sid_to_shard.copy()
        out["plan/sid_to_local"] = plan.sid_to_local.copy()
        out["plan/sid_to_flat"] = plan.sid_to_flat.copy()
        out["plan/local_to_sid"] = plan.local_to_sid.copy()
    for k in ("pending/sid", "pending/vals", "pending/ts", "pending/its"):
        out[k] = np.array(arrays[k])

    new_meta = dict(meta)
    new_meta["registry"] = dict(meta["registry"])
    new_meta["registry"]["cfg"] = dataclasses.asdict(new_cfg)
    new_meta["kind"] = "sharded" if n_shards > 1 else "single"
    return out, new_meta


# --------------------------------------------------------------------------
# the sharded step
# --------------------------------------------------------------------------

def make_shard_round(
    cfg: EngineConfig,
    plan: ShardPlan,
    fanout_fn: Callable = fanout_reference,
    fused: Optional[bool] = None,
) -> Callable:
    """The per-shard round body shared by the sharded step and the sharded
    superstep scan: ``round(tables, gmap, state, ingest) -> (state, sink)``
    over *local* (no leading shard axis) views, collectives inside.

    Exchange buffers & overflow accounting: stage 1 produces up to
    ``cfg.work`` work items per shard; each is bound for the shard owning
    its target sid.  They are compacted into an ``(n_shards, exchange)``
    buffer — ``cfg.exchange`` rows per destination, in batch order — and
    swapped with one ``all_to_all``.  Items beyond a destination's rows
    are counted into ``stats["dropped_overflow"]`` on the *sending* shard
    (never silently lost); ``cfg.exchange_slots=0`` sizes the buffers so
    overflow is impossible, the precondition for bit-exact equivalence
    with the single-device engine.

    ``fused`` (default ``cfg.fused_round``) selects the round-fusion
    plane: the exchange compaction and the post-exchange fetch+VM+window
    stage run through :mod:`repro.kernels.round_fuse` (Pallas kernels on
    TPU, fused jnp refs elsewhere) and the enqueue sites use the fast
    free-slot search.  The ``all_to_all`` itself cannot fuse — it is the
    shard boundary — so the sharded fusion is the two halves around it.
    Bit-identical to the staged body for fusable programs only (the host
    engine checks and falls back)."""
    n_shards, n_local = plan.n_shards, plan.n_local
    N, C, F = cfg.n_streams, cfg.channels, cfg.max_out
    B, W = cfg.batch, cfg.work
    E = cfg.exchange                      # per-destination exchange rows
    WR = n_shards * E                     # work width after the exchange
    if fused is None:
        fused = cfg.fused_round
    fused = fused and cfg.scheduler == "packed"
    if fused:
        from repro.kernels.round_fuse.ops import (apply_programs,
                                                  exchange_compact)
        from repro.kernels.round_fuse.ref import RegLayout
        layout = RegLayout.from_cfg(cfg)

    def shard_round(tables: DeviceTables, gmap: GlobalMaps,
                    state: EngineState, ingest: IngestBatch):
        stats = dict(state.stats)
        # tenant of every *global* sid (queues/exchange carry global sids);
        # this shard's queue only ever holds sids it owns, so the local
        # tenant table resolves them
        tenant_by_sid = tables.tenant[
            jnp.clip(gmap.sid_to_local, 0, n_local - 1)]

        # ---- phase 0: ingest SUs routed to this shard (global sids),
        # quota-gated against this shard's token buckets ------------------
        with jax.named_scope("ingest"):
            g_sid = jnp.clip(ingest.sid, 0, N - 1)
            l_sid = jnp.clip(gmap.sid_to_local[g_sid], 0, n_local - 1)
            state, stats = ingest_phase(state, stats, ingest, l_sid, g_sid,
                                        tables.active[l_sid], n_local,
                                        tables.tenant[l_sid],
                                        tables.quota, tables.burst,
                                        fast_free=fused,
                                        quarantined=state.quarantined[l_sid])

        # ---- pop this round's events (weighted-fair; global sids) -------
        with jax.named_scope("pop_accounting"):
            state, (e_sid, e_vals, e_ts, e_its, e_pop) = _pop(
                state, gmap.priority, B, tenant_by_sid, tables.weight,
                cfg.scheduler)
            stats["popped"] += e_pop.sum(dtype=jnp.int32)
            e_loc = jnp.clip(gmap.sid_to_local[jnp.clip(e_sid, 0, N - 1)],
                             0, n_local - 1)
            # events whose stream was revoked (or quarantined) while queued
            # drop here; the two classes are accounted separately
            e_act = tables.active[e_loc]
            e_poison = e_pop & e_act & state.quarantined[e_loc]
            e_valid = e_pop & e_act & ~state.quarantined[e_loc]
            stats["dropped_revoked"] += (e_pop & ~e_act).sum(dtype=jnp.int32)
            state = dlq_append(state, e_sid, e_vals, e_ts,
                               tenant_by_sid[jnp.clip(e_sid, 0, N - 1)],
                               DLQ_REVOKED, e_pop & ~e_act, its=e_its)
            stats["dropped_poisoned"] += e_poison.sum(dtype=jnp.int32)
            state = dlq_append(state, e_sid, e_vals, e_ts,
                               tenant_by_sid[jnp.clip(e_sid, 0, N - 1)],
                               DLQ_POISONED, e_poison, its=e_its)

        # ---- post-ingest snapshot: the lock-free global view ------------
        with jax.named_scope("snapshot"):
            vals_all = jax.lax.all_gather(state.values, AXIS)
            ts_all = jax.lax.all_gather(state.timestamps, AXIS)
            values_by_sid = vals_all.reshape(n_shards * n_local,
                                             C)[gmap.sid_to_flat]
            ts_by_sid = ts_all.reshape(n_shards * n_local)[gmap.sid_to_flat]

        # ---- stage 1: fan-out via the shard-local out-tables ------------
        with jax.named_scope("fanout"):
            targets, _ = fanout_fn(e_loc, e_ts, e_valid,
                                   tables.out_table, ts_by_sid,
                                   with_early=False)
            wi_t = targets.reshape(W)
            wi_valid = (wi_t >= 0) & jnp.repeat(e_valid, F)
            wi_src = jnp.repeat(e_sid, F)
            wi_vals = jnp.repeat(e_vals, F, axis=0)
            wi_ts = jnp.repeat(e_ts, F)
            wi_its = jnp.repeat(e_its, F)

        # ---- exchange stage: route work items to the target's owner -----
        # One-pass compaction: a single running per-destination count gives
        # every item its rank within its destination bucket, then one
        # scatter packs all buckets at once (slot layout — and therefore
        # results — bit-identical to the former per-destination loop).
        with jax.named_scope("exchange"):
            t_safe = jnp.clip(wi_t, 0, N - 1)
            dest_shard = jnp.where(wi_valid, gmap.sid_to_shard[t_safe],
                                   n_shards)
            if fused:
                xi, xf, x_drop = exchange_compact(wi_t, wi_src, wi_ts,
                                                  wi_its, wi_vals,
                                                  dest_shard, n_shards, E)
            else:
                payload_i = jnp.stack([wi_t, wi_src, wi_ts, wi_its],
                                      axis=-1)                       # (W, 4)
                routed = dest_shard < n_shards
                d_safe = jnp.clip(dest_shard, 0, n_shards - 1)
                # unrouted items must not consume bucket ranks: mask them
                # out of the running count (their own rank reads garbage,
                # gated)
                onehot = routed[:, None] & \
                    (d_safe[:, None] == jnp.arange(n_shards)[None, :])
                rank = jnp.take_along_axis(
                    jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1,
                    d_safe[:, None], axis=1)[:, 0]                   # (W,)
                fits = routed & (rank < E)
                slot = jnp.where(fits, d_safe * E + rank, n_shards * E)
                xi = jnp.full((n_shards * E, 4), -1, jnp.int32) \
                    .at[slot].set(payload_i, mode="drop") \
                    .reshape(n_shards, E, 4)
                xf = jnp.zeros((n_shards * E, C), jnp.float32) \
                    .at[slot].set(wi_vals, mode="drop") \
                    .reshape(n_shards, E, C)
                x_drop = routed & ~fits
            stats["dropped_overflow"] += x_drop.sum(dtype=jnp.int32)
            # exchange-slot contention is attributable per tenant: charge
            # the *emitting* stream's owner (wi_src is always owned by this
            # shard, so the local tenant map resolves it; the flooding
            # producer pays, consistent with queue-overflow and quota
            # accounting)
            Tn = cfg.n_tenants
            src_safe = jnp.clip(wi_src, 0, N - 1)
            state = state._replace(
                tenant_dropped_overflow=state.tenant_dropped_overflow.at[
                    jnp.where(x_drop, tenant_by_sid[src_safe], Tn)
                ].add(1, mode="drop"))
            state = dlq_append(state, wi_src, wi_vals, wi_ts,
                               tenant_by_sid[src_safe], DLQ_OVERFLOW, x_drop,
                               its=wi_its)

            ri = jax.lax.all_to_all(xi, AXIS, split_axis=0, concat_axis=0)
            rf = jax.lax.all_to_all(xf, AXIS, split_axis=0, concat_axis=0)
            r_t = ri[..., 0].reshape(WR)
            r_src = ri[..., 1].reshape(WR)
            r_ts = ri[..., 2].reshape(WR)
            r_its = ri[..., 3].reshape(WR)
            r_vals = rf.reshape(WR, C)
            r_valid = r_t >= 0
            rt_safe = jnp.clip(r_t, 0, N - 1)
            r_loc = jnp.clip(gmap.sid_to_local[rt_safe], 0, n_local - 1)

        # ---- stages 2 + 3 (shared with the single-device engine) --------
        # quarantined rows are masked out of the effective active plane, so
        # a poisoned stream neither stores nor emits while tripped
        with jax.named_scope("apply"):
            eff_active = tables.active & ~state.quarantined
            if fused:
                # the local tables (n_local rows) and the global snapshot
                # (N rows) are two row spaces, which the apply kernel does
                # not take: this stage runs the fused jnp reference on
                # every backend
                new_vals, ts_out, live, keep, keep_ts, passf, badf = \
                    apply_programs(layout, tables.in_table, tables.progs,
                                   tables.consts, tables.is_composite,
                                   eff_active, r_loc, rt_safe, r_src,
                                   r_vals, r_ts, r_valid,
                                   values_by_sid, ts_by_sid,
                                   use_kernel=False)
                stats["processed"] += live.sum(dtype=jnp.int32)
                stats["discarded_stale"] += \
                    (live & ~keep_ts).sum(dtype=jnp.int32)
                stats["filtered"] += \
                    (live & keep_ts & ~passf).sum(dtype=jnp.int32)
                stats["nonfinite"] += (badf & r_valid).sum(dtype=jnp.int32)
            else:
                new_vals, ts_out, live, keep, counts, badf = \
                    process_work_items(cfg, tables._replace(active=eff_active),
                                       r_loc, rt_safe, r_src, r_vals, r_ts,
                                       r_valid, values_by_sid, ts_by_sid)
                for k, v in counts.items():
                    stats[k] = stats[k] + v

        # ---- stage 4: store into this shard's slice ----------------------
        # (winners re-enqueue into the local queue; the sink is per-shard)
        with jax.named_scope("store_emit"):
            state, stats, sink = store_and_emit(cfg, tables, state, stats,
                                                r_loc, r_t, r_src, new_vals,
                                                ts_out, keep, n_local,
                                                fast_free=fused, wi_its=r_its)

        # ---- fault plane: breaker window + device auto-quarantine -------
        # amplification is detected at the dispatch site (the source shard
        # owns the popped sid); non-finite results are detected after the
        # exchange on the shard owning the target row — each fault lands
        # on its row's owner, so the breaker state never needs collectives
        with jax.named_scope("fault"):
            fan = (wi_t.reshape(B, F) >= 0).sum(axis=1, dtype=jnp.int32)
            fault_evt = fault_events(tables.breaker, badf, r_valid, r_loc,
                                     fan, e_valid, e_loc, n_local)
            q_row = jnp.clip(
                gmap.sid_to_local[jnp.clip(state.q_sid, 0, N - 1)],
                0, n_local - 1)
            state, stats = fault_phase(state, stats, tables.breaker,
                                       fault_evt, tables.active,
                                       tables.tenant, q_row)
            state = state._replace(
                stats=stats,
                tenant_queued=tenant_occupancy(state, tenant_by_sid,
                                               cfg.n_tenants))
        return state, sink

    return shard_round


def make_sharded_step(
    cfg: EngineConfig,
    plan: ShardPlan,
    mesh: Mesh,
    fanout_fn: Callable = fanout_reference,
    donate: bool = True,
    fused: Optional[bool] = None,
) -> Callable:
    """Build the jitted sharded round.  Signature:
    ``step(tables, gmap, state, ingest) -> (state, sink)`` where every
    ``tables``/``state``/``ingest``/``sink`` leaf carries a leading
    ``(n_shards,)`` axis and ``gmap`` is replicated.  The round body (and
    its exchange-stage semantics) is :func:`make_shard_round`."""
    shard_round = make_shard_round(cfg, plan, fanout_fn, fused)

    def shard_step(tables: DeviceTables, gmap: GlobalMaps,
                   state: EngineState, ingest: IngestBatch):
        tables = jax.tree.map(lambda x: x[0], tables)
        state = jax.tree.map(lambda x: x[0], state)
        ingest = jax.tree.map(lambda x: x[0], ingest)
        state, sink = shard_round(tables, gmap, state, ingest)
        return (jax.tree.map(lambda x: x[None], state),
                jax.tree.map(lambda x: x[None], sink))

    sharded = P(AXIS)
    fn = jax.shard_map(shard_step, mesh=mesh,
                       in_specs=(sharded, P(), sharded, sharded),
                       out_specs=(sharded, sharded), check_vma=False)
    # pinned output sharding: zero-size leaves (retention/DLQ rings when
    # off) would otherwise come back replicated, and the next call (or
    # table edit) would see differently-sharded inputs
    return jax.jit(fn, donate_argnums=(2,) if donate else (),
                   out_shardings=NamedSharding(mesh, sharded))


def make_sharded_superstep(
    cfg: EngineConfig,
    plan: ShardPlan,
    mesh: Mesh,
    K: int,
    fanout_fn: Callable = fanout_reference,
    donate: bool = True,
    fused: Optional[bool] = None,
) -> Callable:
    """Fuse K sharded rounds into one compiled ``lax.scan`` under
    ``shard_map`` — the exchange stage (and its collectives) runs *inside*
    the scan, so a whole superstep costs one dispatch and zero
    device->host round-trips.  Signature: ``superstep(tables, gmap, state,
    ring) -> (state, spool, ring)`` with per-shard leading axes on
    everything but the replicated ``gmap``; ``ring`` holds each shard's
    pre-routed (K, B) ingest grid (see ``ShardedStreamEngine._stage``)."""
    assert K >= 1
    shard_round = make_shard_round(cfg, plan, fanout_fn, fused)
    B, C = cfg.batch, cfg.channels
    P_spool = cfg.spool_slots(K)

    def shard_superstep(tables: DeviceTables, gmap: GlobalMaps,
                        state: EngineState, ring: IngestRing):
        tables = jax.tree.map(lambda x: x[0], tables)
        state = jax.tree.map(lambda x: x[0], state)
        ring = jax.tree.map(lambda x: x[0], ring)
        tenant_by_sid = tables.tenant[
            jnp.clip(gmap.sid_to_local, 0, plan.n_local - 1)]
        state, spool, ring = scan_rounds(
            lambda st, ing: shard_round(tables, gmap, st, ing),
            state, ring, K, B, C, P_spool, tenant_by_sid)
        return (jax.tree.map(lambda x: x[None], state),
                jax.tree.map(lambda x: x[None], spool),
                jax.tree.map(lambda x: x[None], ring))

    sharded = P(AXIS)
    fn = jax.shard_map(shard_superstep, mesh=mesh,
                       in_specs=(sharded, P(), sharded, sharded),
                       out_specs=(sharded, sharded, sharded),
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(2, 3) if donate else (),
                   out_shardings=NamedSharding(mesh, sharded))


# --------------------------------------------------------------------------
# host-side wrapper
# --------------------------------------------------------------------------

class ShardedStreamEngine(StreamEngine):
    """Drop-in :class:`StreamEngine` running the pub/sub plane sharded over
    ``cfg.n_shards`` devices.  Public API (post/round/drain/value_of/ts_of/
    counters/inject_code/rewire + the live admission methods) matches the
    single-device engine; admissions additionally route the new sid to a
    shard and :meth:`rebalance` fights occupancy skew."""

    def __init__(self, registry: Registry, *, mesh: Optional[Mesh] = None,
                 fanout_fn: Callable = fanout_reference,
                 priority: Optional[np.ndarray] = None):
        cfg = registry.cfg
        self.cfg = cfg
        self.registry = registry
        self._bind_mesh(mesh)
        host_tables, self.plan = registry.build_sharded_tables(priority)
        self.tables = jax.device_put(DeviceTables.from_host(host_tables),
                                     self._shard)
        self.gmap = jax.device_put(GlobalMaps.build(priority, self.plan),
                                   self._repl)
        self.state = jax.device_put(sharded_init_state(cfg, self.plan),
                                    self._shard)
        self._fanout_fn = fanout_fn
        self._refresh_fusable()
        self._fn_cache = {}
        self._compiled_for(
            self._layout_key(self.plan),
            lambda fused: make_sharded_step(cfg, self.plan, self.mesh,
                                            fanout_fn, fused=fused))
        self._pending: List[List] = []
        self.admission_rejected = 0
        self._rounds_done = 0
        self._last_base = 0
        self._ring = None
        self._ring_K = 0
        self._ring_free: List[List[int]] = []
        self._ring_dirty = False    # placement changed: re-stage everything
        self._ckpt = None
        self._steps_done = 0
        self._init_slots()

    def _bind_mesh(self, mesh: Optional[Mesh]) -> None:
        """Resolve (or validate) the 1-D device mesh for ``cfg.n_shards``
        and derive the step shardings.  Shared by ``__init__`` and
        :meth:`StreamEngine.resize`, which re-binds after morphing an
        engine to a new shard count."""
        cfg = self.cfg
        if mesh is None:
            devs = jax.devices()
            if len(devs) < cfg.n_shards:
                raise ValueError(
                    f"n_shards={cfg.n_shards} but only {len(devs)} devices; "
                    "on CPU set XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=<n> before importing jax")
            mesh = Mesh(np.asarray(devs[:cfg.n_shards]), (AXIS,))
        if AXIS not in mesh.shape or mesh.shape[AXIS] != cfg.n_shards:
            raise ValueError(
                f"mesh axes {dict(mesh.shape)} do not provide "
                f"'{AXIS}'={cfg.n_shards} required by cfg.n_shards")
        self.mesh = mesh
        # place everything with its step sharding up front so the jitted
        # round never re-broadcasts tables/state from one device
        self._shard = NamedSharding(mesh, P(AXIS))
        self._repl = NamedSharding(mesh, P())

    def _init_slots(self) -> None:
        """(Re)build the per-shard free-slot bookkeeping from the registry:
        ``_occupancy[s]`` live streams on shard ``s``, ``_spare[s]`` the
        sorted inactive sids placed there (swap partners for incoming
        placements), ``_holes[s]`` the physical rows no sid maps to at all
        (cheapest landing slots — common under the tenant partition, whose
        per-shard row counts are padded to the largest bucket)."""
        S = self.plan.n_shards
        self._occupancy = np.zeros((S,), np.int64)
        self._spare: List[List[int]] = [[] for _ in range(S)]
        self._holes: List[List[int]] = [
            sorted(np.nonzero(self.plan.local_to_sid[s] < 0)[0].tolist())
            for s in range(S)]
        streams = self.registry.streams
        for sid in range(self.cfg.n_streams):
            shard = int(self.plan.sid_to_shard[sid])
            if sid < len(streams) and streams[sid] is not None:
                self._occupancy[shard] += 1
            else:
                self._spare[shard].append(sid)

    # -------------------------------------------------------------- ingest
    def _take_ingest(self) -> IngestBatch:
        """Admit at most one pending SU per stream (like the base engine),
        then route each SU to its owner shard, preserving batch order."""
        batch = StreamEngine._take_ingest(self)
        B, C, S = self.cfg.batch, self.cfg.channels, self.plan.n_shards
        # route on the same clipped sid the per-shard step will store to
        sid = np.clip(np.asarray(batch.sid), 0, self.cfg.n_streams - 1)
        vals = np.asarray(batch.vals)
        ts = np.asarray(batch.ts)
        valid = np.asarray(batch.valid)
        its = np.asarray(batch.its)
        r_sid = np.zeros((S, B), np.int32)
        r_vals = np.zeros((S, B, C), np.float32)
        r_ts = np.zeros((S, B), np.int32)
        r_valid = np.zeros((S, B), bool)
        r_its = np.zeros((S, B), np.int32)
        fill = np.zeros((S,), np.int64)
        for i in np.nonzero(valid)[0]:
            s = int(self.plan.sid_to_shard[sid[i]])
            j = fill[s]
            r_sid[s, j], r_vals[s, j], r_ts[s, j] = sid[i], vals[i], ts[i]
            r_its[s, j] = its[i]
            r_valid[s, j] = True
            fill[s] += 1
        return jax.device_put(
            IngestBatch(r_sid, r_vals, r_ts, r_valid, r_its), self._shard)

    # --------------------------------------------------------------- rounds
    def round(self) -> SinkBatch:
        self._last_base = self._rounds_done
        self.state, sink = self._step(self.tables, self.gmap, self.state,
                                      self._take_ingest())
        self._rounds_done += 1
        self._maybe_checkpoint()
        return SinkBatch(*(x.reshape((-1,) + x.shape[2:]) for x in sink))

    # ----------------------------------------------------------- supersteps
    def _layout_key(self, plan):
        """Cache key for the compiled closures: everything they are
        specialized on.  The step is shaped by the shard/row counts and
        the mesh devices — plan *content* is runtime data (see rewire)."""
        return ("sharded", plan.n_shards, plan.n_local,
                tuple(int(d.id) for d in self.mesh.devices.flat))

    def _superstep_fn(self, K: int):
        fn = self._superstep_fns.get(K)
        if fn is None:
            fn = self._superstep_fns[K] = make_sharded_superstep(
                self.cfg, self.plan, self.mesh, K, self._fanout_fn,
                fused=self._path == "fused")
        return fn

    def _release_ring_slot(self, slot) -> None:
        s, j = slot
        self._ring_free[s].append(j)

    def _stage(self, K: int) -> Tuple[int, int, int]:
        """Superstep boundary, sharded: assign rounds exactly like K
        sequential ``_take_ingest`` calls and route every staged SU to
        its owner shard's ring slice.  The per-shard ring layout (and its
        sharding) is *cached* across boundaries: carried SUs keep their
        resident payloads and only the small routing-tag planes travel
        again — new payloads plus all tags ship pre-placed in one
        ``device_put``, then one jitted vmapped edit
        (:func:`_stage_ring_op`) applies them, mirroring the
        single-device ``stage_ring`` boundary.  Placement changes
        (admission routing, ``rebalance``, ``rewire``) set
        ``_ring_dirty``, which voids the cache — the next boundary
        re-stages everything from the host copy, so a moved sid can
        never consume a stale shard's slot.  Returns the
        ``repro.stage`` span's counts, as the single-device ``_stage``."""
        S, R, C = self.plan.n_shards, self.cfg.ring_slots(K), self.cfg.channels
        N = self.cfg.n_streams
        if self._ring is None or self._ring_K != K or self._ring_dirty:
            self._ring = jax.device_put(IngestRing(
                sid=np.zeros((S, R), np.int32),
                vals=np.zeros((S, R, C), np.float32),
                ts=np.zeros((S, R), np.int32),
                its=np.zeros((S, R), np.int32),
                rnd=np.full((S, R), K, np.int32),
                pos=np.zeros((S, R), np.int32),
                valid=np.zeros((S, R), bool)), self._shard)
            self._ring_K = K
            self._ring_free = [list(range(R)) for _ in range(S)]
            for e in self._pending:     # slots of the old ring are void
                e[3] = None
            self._ring_dirty = False

        def shard_of(e):
            # route on the same clipped sid the per-shard step stores to
            return int(self.plan.sid_to_shard[min(max(int(e[0]), 0), N - 1)])

        assigned = self._assign_rounds(K)
        carried = [e for e in self._pending if e[3] is not None]
        writes = []
        for e, _k, _i in assigned:
            s = shard_of(e)
            if e[3] is not None and e[3][0] != s:   # placement moved and the
                self._ring_free[e[3][0]].append(e[3][1])   # dirty reset
                e[3] = None                          # missed it: release the
            if e[3] is None:                         # stale shard's slot and
                if self._ring_free[s]:               # re-ship
                    e[3] = (s, self._ring_free[s].pop())
                else:           # youngest carried SU on s spills its slot
                    victim = next(x for x in reversed(carried)
                                  if x[3] is not None and x[3][0] == s)
                    e[3], victim[3] = victim[3], None
                writes.append(e)
        for e in self._pending:     # pre-ship: earliest carried SUs claim
            if e[3] is None:        # leftover slots, cutting future ships
                s = shard_of(e)
                if self._ring_free[s]:
                    e[3] = (s, self._ring_free[s].pop())
                    writes.append(e)
        w_slot = np.full((S, R), R, np.int32)
        w_sid = np.zeros((S, R), np.int32)
        w_vals = np.zeros((S, R, C), np.float32)
        w_ts = np.zeros((S, R), np.int32)
        w_its = np.zeros((S, R), np.int32)
        wn = np.zeros((S,), np.int64)
        for e in writes:
            s, j = e[3]
            q = int(wn[s]); wn[s] += 1
            w_slot[s, q], w_sid[s, q] = j, min(max(int(e[0]), 0), N - 1)
            w_vals[s, q], w_ts[s, q], w_its[s, q] = e[1], e[2], e[4]
        rnd = np.full((S, R), K, np.int32)
        pos = np.zeros((S, R), np.int32)
        valid = np.zeros((S, R), bool)
        col: dict = {}                        # (shard, round) -> next column
        for e, k, _i in assigned:             # (round, take-order) order
            s, j = e[3]
            c = col.get((s, k), 0); col[(s, k)] = c + 1
            rnd[s, j], pos[s, j], valid[s, j] = k, c, True
        for e in self._pending:
            if e[3] is not None:
                s, j = e[3]
                valid[s, j] = True            # carried overflow stays resident
        args = jax.device_put((w_slot, w_sid, w_vals, w_ts, w_its,
                               rnd, pos, valid), self._shard)
        self._ring = _stage_ring_op(self._ring, *args)
        for e, _k, _i in assigned:            # consumed by this superstep:
            s, j = e[3]                       # slots reusable next boundary
            self._ring_free[s].append(j)
        return len(assigned), len(writes), len(self._pending)

    def _run_superstep(self, K: int) -> SinkSpool:
        self.state, spool, self._ring = self._superstep_fn(K)(
            self.tables, self.gmap, self.state, self._ring)
        return spool

    def spool_sinks(self, spool: SinkSpool, K=None) -> List[SinkBatch]:
        """Per-round SinkBatches from the per-shard spools — each round's
        batch is the shard-concatenated layout ``round()`` returns.  Host
        spans as the single-device ``spool_sinks``."""
        S, C = self.cfg.sink_buffer, self.cfg.channels
        n_sh = self.plan.n_shards
        with jax.profiler.TraceAnnotation("repro.spool.read") as span:
            sid = np.asarray(spool.sid)
            vals = np.asarray(spool.vals)
            ts = np.asarray(spool.ts)
            its = np.asarray(spool.its)
            rnd = np.asarray(spool.rnd)
            fill = np.asarray(spool.fill)
            span.set_metadata(records=int(fill.sum()))
        K = K or self._ring_K or 1
        sinks = []
        with jax.profiler.TraceAnnotation("repro.spool.decode"):
            for k in range(K):
                b_sid = np.zeros((n_sh * S,), np.int32)
                b_vals = np.zeros((n_sh * S, C), np.float32)
                b_ts = np.zeros((n_sh * S,), np.int32)
                b_valid = np.zeros((n_sh * S,), bool)
                b_its = np.zeros((n_sh * S,), np.int32)
                for s in range(n_sh):
                    idx = np.nonzero(rnd[s, :fill[s]] == k)[0]
                    n = len(idx)
                    b_sid[s * S:s * S + n] = sid[s, idx]
                    b_vals[s * S:s * S + n] = vals[s, idx]
                    b_ts[s * S:s * S + n] = ts[s, idx]
                    b_its[s * S:s * S + n] = its[s, idx]
                    b_valid[s * S:s * S + n] = True
                sinks.append(SinkBatch(b_sid, b_vals, b_ts, b_valid, b_its))
        return sinks

    # ------------------------------------------------- dynamic admission
    def _table_row(self, sid: int):
        return (np.int32(self.plan.sid_to_shard[sid]),
                np.int32(self.plan.sid_to_local[sid]))

    def _swap_placement(self, a: int, b: int) -> None:
        """Exchange the physical slots of two sids in the host plan (both
        must be inert on device: inactive rows, or drained active rows that
        :func:`admission.migrate_row` just moved)."""
        p = self.plan
        for arr in (p.sid_to_shard, p.sid_to_local, p.sid_to_flat):
            arr[a], arr[b] = int(arr[b]), int(arr[a])
        p.local_to_sid[p.sid_to_shard[a], p.sid_to_local[a]] = a
        p.local_to_sid[p.sid_to_shard[b], p.sid_to_local[b]] = b

    def _set_gmap(self, sid: int, priority: int) -> None:
        self.gmap = _place_sid_op(
            self.gmap, np.int32(sid),
            np.int32(self.plan.sid_to_shard[sid]),
            np.int32(self.plan.sid_to_local[sid]),
            np.int32(self.plan.n_local), np.int32(priority))

    def _claim_slot(self, sid: int, want: int) -> Optional[int]:
        """Claim a physical slot on shard ``want`` for ``sid``: an unmapped
        hole when one exists, otherwise a swap with a spare (inactive) sid
        placed there.  Updates the host plan only; the caller migrates the
        device rows when ``sid`` is active.  Returns the swap partner, or
        ``None`` for a hole claim."""
        p = self.plan
        cur, cur_l = int(p.sid_to_shard[sid]), int(p.sid_to_local[sid])
        if self._holes[want]:
            loc = self._holes[want].pop(0)
            p.sid_to_shard[sid], p.sid_to_local[sid] = want, loc
            p.sid_to_flat[sid] = want * p.n_local + loc
            p.local_to_sid[want, loc] = sid
            p.local_to_sid[cur, cur_l] = -1
            bisect.insort(self._holes[cur], cur_l)
            return None
        partner = self._spare[want].pop(0)
        self._swap_placement(sid, partner)
        bisect.insort(self._spare[cur], partner)
        return partner

    def _free_slots(self, shard: int) -> int:
        return len(self._holes[shard]) + len(self._spare[shard])

    def _place_sid(self, sid: int, tid: int, priority: int) -> None:
        """Route a newly admitted sid to a shard: the ``"tenant"``
        partition keeps the tenant's pipeline co-located (tid hash), the
        ``"block"`` partition targets the least-loaded shard.  When the
        target differs from the sid's planned slot, the sid claims a hole
        or swaps with a spare sid there — all rows involved are inert, so
        placement is pure bookkeeping plus a replicated gmap edit."""
        S = self.plan.n_shards
        cur = int(self.plan.sid_to_shard[sid])
        self._spare[cur].remove(sid)
        if self.cfg.partition == "tenant":
            want = tid % S
        else:
            cand = [s for s in range(S) if s == cur or self._free_slots(s)]
            want = min(cand, key=lambda s: (self._occupancy[s], s))
        if want != cur and self._free_slots(want):
            partner = self._claim_slot(sid, want)
            if partner is not None:
                self._set_gmap(partner, 0)
            cur = want
            self._ring_dirty = True     # sid routing moved: void ring cache
        self._occupancy[cur] += 1
        self._set_gmap(sid, priority)

    def _released_sid(self, sid: int) -> None:
        shard = int(self.plan.sid_to_shard[sid])
        self._occupancy[shard] -= 1
        bisect.insort(self._spare[shard], sid)

    def _sync_admitted(self) -> None:
        # re-pin the round's input shardings after a table edit so the
        # compiled step always sees the exact avals it was traced for
        # (zero-retrace invariant of the admission plane)
        self.tables = jax.device_put(self.tables, self._shard)
        self.state = jax.device_put(self.state, self._shard)
        self.gmap = jax.device_put(self.gmap, self._repl)

    def rebalance(self, tolerance: int = 1) -> int:
        """Migrate streams from overfull to underfull shards until the
        per-shard occupancy spread is ≤ ``tolerance``; returns the number
        of migrations.  Each move is one :func:`admission.migrate_row`
        table edit (the state slice travels with the row) plus a gmap
        update — no recompilation.  Queues must be drained: in-flight SUs
        reference the old placement."""
        if bool(np.asarray(self.state.q_valid).any()) or self._pending:
            raise ValueError(
                "rebalance() while SUs are in flight; drain() first")
        moved = 0
        prio = np.asarray(self.gmap.priority)
        while True:
            hi = int(np.argmax(self._occupancy))
            lo = int(np.argmin(self._occupancy))
            if self._occupancy[hi] - self._occupancy[lo] <= tolerance \
                    or not self._free_slots(lo):
                break
            # deterministic pick: the highest active sid on the full shard
            sid = max(s for s in range(self.cfg.n_streams)
                      if int(self.plan.sid_to_shard[s]) == hi
                      and s < len(self.registry.streams)
                      and self.registry.streams[s] is not None)
            src_row = self._table_row(sid)
            partner = self._claim_slot(sid, lo)
            self.tables, self.state = admission.migrate_row(
                self.tables, self.state, src_row, self._table_row(sid))
            self._occupancy[hi] -= 1
            self._occupancy[lo] += 1
            if partner is not None:
                self._set_gmap(partner, 0)
            self._set_gmap(sid, int(prio[sid]))
            moved += 1
        if moved:
            self._ring_dirty = True
            self._sync_admitted()
        return moved

    def rewire(self) -> None:
        """Re-lower after subscribe()/new streams.  With the "tenant"
        partition, newly created streams can change the sid placement; the
        per-sid state is then permuted into the new layout (queues must be
        empty — in-flight SUs cannot migrate shards)."""
        prio = np.asarray(self.gmap.priority)
        host_tables, new_plan = self.registry.build_sharded_tables(prio)
        old = self.plan
        moved = (new_plan.n_local != old.n_local
                 or (new_plan.sid_to_flat != old.sid_to_flat).any())
        if moved:
            if bool(np.asarray(self.state.q_valid).any()) or self._pending:
                raise ValueError(
                    "rewire() changed stream placement while SUs are in "
                    "flight; drain() before rewiring")
            S, L, C = new_plan.n_shards, new_plan.n_local, self.cfg.channels
            Rr = self.cfg.retention_slots
            v = np.zeros((S * L, C), np.float32)
            ts = np.full((S * L,), INT_MIN, np.int32)
            rv = np.zeros((S * L, Rr, C), np.float32)
            rt = np.zeros((S * L, Rr), np.int32)
            ri = np.zeros((S * L, Rr), np.int32)
            rc = np.zeros((S * L,), np.int32)
            v[new_plan.sid_to_flat] = np.asarray(
                self.state.values).reshape(-1, C)[old.sid_to_flat]
            ts[new_plan.sid_to_flat] = np.asarray(
                self.state.timestamps).reshape(-1)[old.sid_to_flat]
            F_old = old.n_shards * old.n_local  # explicit: -1 fails at Rr=0
            rv[new_plan.sid_to_flat] = np.asarray(
                self.state.ret_vals).reshape(F_old, Rr, C)[old.sid_to_flat]
            rt[new_plan.sid_to_flat] = np.asarray(
                self.state.ret_ts).reshape(F_old, Rr)[old.sid_to_flat]
            ri[new_plan.sid_to_flat] = np.asarray(
                self.state.ret_its).reshape(F_old, Rr)[old.sid_to_flat]
            rc[new_plan.sid_to_flat] = np.asarray(
                self.state.ret_count).reshape(-1)[old.sid_to_flat]
            # the breaker's per-sid books move with their rows too — a
            # quarantine must stick to its stream across a re-placement
            qr = np.zeros((S * L,), bool)
            fcn = np.zeros((S * L,), np.int32)
            fen = np.zeros((S * L,), np.int32)
            ftn = np.zeros((S * L,), np.int32)
            qr[new_plan.sid_to_flat] = np.asarray(
                self.state.quarantined).reshape(-1)[old.sid_to_flat]
            fcn[new_plan.sid_to_flat] = np.asarray(
                self.state.fault_count).reshape(-1)[old.sid_to_flat]
            fen[new_plan.sid_to_flat] = np.asarray(
                self.state.fault_epoch).reshape(-1)[old.sid_to_flat]
            ftn[new_plan.sid_to_flat] = np.asarray(
                self.state.fault_total).reshape(-1)[old.sid_to_flat]
            self.state = jax.device_put(self.state._replace(
                values=jnp.asarray(v.reshape(S, L, C)),
                timestamps=jnp.asarray(ts.reshape(S, L)),
                ret_vals=jnp.asarray(rv.reshape(S, L, Rr, C)),
                ret_ts=jnp.asarray(rt.reshape(S, L, Rr)),
                ret_its=jnp.asarray(ri.reshape(S, L, Rr)),
                ret_count=jnp.asarray(rc.reshape(S, L)),
                quarantined=jnp.asarray(qr.reshape(S, L)),
                fault_count=jnp.asarray(fcn.reshape(S, L)),
                fault_epoch=jnp.asarray(fen.reshape(S, L)),
                fault_total=jnp.asarray(ftn.reshape(S, L))), self._shard)
            if L != old.n_local:    # step closures are shaped by n_local
                self._compiled_for(
                    self._layout_key(new_plan),
                    lambda fused: make_sharded_step(self.cfg, new_plan,
                                                    self.mesh,
                                                    self._fanout_fn,
                                                    fused=fused))
        self.plan = new_plan
        qos = self.tables            # weight/quota/burst survive re-lowers
        self.tables = jax.device_put(
            DeviceTables.from_host(host_tables)._replace(
                weight=qos.weight, quota=qos.quota, burst=qos.burst),
            self._shard)
        self.gmap = jax.device_put(GlobalMaps.build(prio, new_plan),
                                   self._repl)
        self._refresh_fusable()
        self._ring_dirty = True         # plan rebuilt: void the ring cache
        self._init_slots()

    # ------------------------------------------------------------- readback
    def value_of(self, stream) -> np.ndarray:
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        sh, lo = self.plan.sid_to_shard[sid], self.plan.sid_to_local[sid]
        return np.asarray(self.state.values[sh, lo])

    def ts_of(self, stream) -> int:
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        sh, lo = self.plan.sid_to_shard[sid], self.plan.sid_to_local[sid]
        return int(self.state.timestamps[sh, lo])

    def counters(self):
        # host-side sum: a device reduction would compile one program per
        # shard count, breaking the zero-retrace contract for pure reads
        return {k: int(np.asarray(v).sum()) for k, v in self.state.stats.items()}

    # ------------------------------------------------- durability & replay
    def snapshot(self):
        """Sharded :meth:`StreamEngine.snapshot`: the base capture (state
        leaves carry their leading shard axis) plus the replicated lookup
        maps and the host placement plan, under ``kind="sharded"``."""
        arrays, meta = StreamEngine.snapshot(self)
        for f in GlobalMaps._fields:
            arrays[f"gmap/{f}"] = np.asarray(getattr(self.gmap, f))
        p = self.plan
        arrays["plan/sid_to_shard"] = p.sid_to_shard.copy()
        arrays["plan/sid_to_local"] = p.sid_to_local.copy()
        arrays["plan/sid_to_flat"] = p.sid_to_flat.copy()
        arrays["plan/local_to_sid"] = p.local_to_sid.copy()
        meta["kind"] = "sharded"
        return arrays, meta

    def _install_snapshot(self, arrays, meta) -> None:
        """Restore half of the sharded :meth:`snapshot`: rebuild the host
        placement plan first (the step program is shaped by ``n_local``),
        then install maps/tables/state/backlog re-pinned to their mesh
        shardings, and rebuild the slot bookkeeping from the restored
        registry."""
        local_to_sid = np.array(arrays["plan/local_to_sid"], np.int32)
        # the snapshot's own layout is authoritative — a snapshot taken at
        # N shards must land in an engine configured for N shards (resize /
        # cross-shard-count restore reshard the snapshot *first*)
        n_shards = int(local_to_sid.shape[0])
        if n_shards != self.cfg.n_shards:
            raise ValueError(
                f"snapshot carries {n_shards} shards but cfg.n_shards="
                f"{self.cfg.n_shards}; reshard_snapshot() it first (or "
                f"restore_engine(..., n_shards=...))")
        plan = ShardPlan(
            n_shards=n_shards,
            n_local=int(local_to_sid.shape[1]),
            sid_to_shard=np.array(arrays["plan/sid_to_shard"], np.int32),
            sid_to_local=np.array(arrays["plan/sid_to_local"], np.int32),
            sid_to_flat=np.array(arrays["plan/sid_to_flat"], np.int32),
            local_to_sid=local_to_sid)
        old = getattr(self, "plan", None)
        if old is None or plan.n_local != old.n_local \
                or plan.n_shards != old.n_shards:
            self._compiled_for(
                self._layout_key(plan),
                lambda fused: make_sharded_step(self.cfg, plan, self.mesh,
                                                self._fanout_fn,
                                                fused=fused))
        self.plan = plan
        self.gmap = GlobalMaps(**{
            f: jnp.asarray(arrays[f"gmap/{f}"])
            for f in GlobalMaps._fields})
        StreamEngine._install_snapshot(self, arrays, meta)
        self._ring_dirty = True
        self._init_slots()

    def _apply_requeue(self, sid, vals, ts, valid, tenant, its) -> None:
        """Route each padded requeue item to its owner shard, then apply
        one :func:`admission.requeue_shard` edit per shard touched (the
        shard index is traced, so churn stays at one trace total)."""
        owner = self.plan.sid_to_shard[
            np.clip(sid, 0, self.cfg.n_streams - 1)]
        for s in sorted(set(owner[valid].tolist())):
            self.state = admission.requeue_shard(
                self.state, jnp.int32(s), jnp.asarray(sid),
                jnp.asarray(vals), jnp.asarray(ts),
                jnp.asarray(valid & (owner == s)), jnp.asarray(tenant),
                its=jnp.asarray(its))
        self._sync_admitted()

    def _apply_respool(self, sid, vals, ts, reason, tenant, its,
                       valid) -> None:
        """Route each refused dead letter back to its owner shard's spool
        (one :func:`admission.respool_shard` edit per shard touched; the
        shard index is traced, so churn stays at one trace total)."""
        owner = self.plan.sid_to_shard[
            np.clip(sid, 0, self.cfg.n_streams - 1)]
        for s in sorted(set(owner[valid].tolist())):
            self.state = admission.respool_shard(
                self.state, jnp.int32(s), jnp.asarray(sid),
                jnp.asarray(vals), jnp.asarray(ts), jnp.asarray(reason),
                jnp.asarray(tenant), jnp.asarray(its),
                jnp.asarray(valid & (owner == s)))
        self._sync_admitted()
