"""The static stream-processing topology (paper §IV-B / §IV-F).

One jit-compiled step implements the four stages common to every pipeline:

    1. subscriber dispatching   (fan-out via the routing tables)
    2. data fetching            (gather co-input last values — lock-free)
    3. transformation & filtering (bytecode VM + Listing-2 consistency)
    4. store, trigger actions and emit

The compiled program is *fixed*; tenants' pipelines — routing tables,
bytecode, constants — are arguments, so creating/rewiring/destroying
pipelines or injecting new user code never recompiles (the paper's core
technique, ported from STORM to XLA).

Batched-round semantics: STORM processes one tuple per bolt invocation; an
XLA program is static dataflow, so each step ingests/pops a *batch* of SUs
and advances every live SU by exactly one hop.  A pipeline of length L
drains in L rounds — preserving the paper's observation (§V-C) that length
is the non-parallelizable dimension while in/out-degree work is parallel.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consistency, program as pvm
from repro.core.config import EngineConfig
from repro.core.registry import CapacityError, EngineTables, Registry

INT_MIN = np.iinfo(np.int32).min + 1
INT_MAX = np.iinfo(np.int32).max

# Virtual-time granularity of the weighted-fair pop: a tenant with weight w
# advances its virtual clock by FAIR_SCALE // w per queued SU, so weights are
# meaningful in [1, FAIR_SCALE] (admission.set_weight clips).  Weight 0 (the
# default) exempts the tenant from shaping entirely — its SUs carry virtual
# tag 0, which makes the all-zero table bit-identical to the pre-QoS
# (priority, seq) FIFO pop.
FAIR_SCALE = 1 << 15

# Within-tenant ranks saturate at RANK_LIM so the virtual tag
# ``rank * FAIR_SCALE // weight`` stays inside int32 at any queue depth and
# any weight (beyond ~64k queued SUs per tenant the tags plateau and ties
# fall back to seq — still starvation-free).  Both scheduler paths apply the
# same clamp (repro.kernels.sched_pop.ref mirrors this constant), which is
# what keeps them bit-identical at the boundary
# (tests/test_sched_pop.py::test_rank_clamp_boundary).
RANK_LIM = INT_MAX // FAIR_SCALE - 1


class DeviceTables(NamedTuple):
    """Device image of :class:`~repro.core.registry.EngineTables`: the
    per-stream routing/program tables (leading dim ``n_streams``, or
    ``(n_shards, n_local)`` under the sharded layout) plus the per-tenant
    QoS tables (leading dim ``n_tenants``, replicated per shard).  All of
    it is *data* to the compiled round — every field can be edited live by
    :mod:`repro.core.admission` ops with zero retraces."""
    in_table: jnp.ndarray      # (N, max_in) int32 input sids, -1 pad
    in_count: jnp.ndarray      # (N,) int32
    out_table: jnp.ndarray     # (N, max_out) int32 subscriber sids, -1 pad
    out_count: jnp.ndarray     # (N,) int32
    progs: jnp.ndarray         # (N, prog_len, 4) int32 VM bytecode
    consts: jnp.ndarray        # (N, n_consts) float32 constant pools
    is_composite: jnp.ndarray  # (N,) bool
    tenant: jnp.ndarray        # (N,) int32 owning tenant id
    priority: jnp.ndarray      # (N,) int32, lower = served first (§IV-E)
    n_channels: jnp.ndarray    # (N,) int32
    model_backed: jnp.ndarray  # (N,) bool — serviced by the model plane
    active: jnp.ndarray        # (N,) live-row mask; admission flips it live
    # ---- tenant QoS plane (per-tenant, NOT per-stream) ------------------
    weight: jnp.ndarray        # (T,) int32 fair-share weight; 0 = unshaped
    quota: jnp.ndarray         # (T,) int32 tokens refilled/round; 0 = no cap
    burst: jnp.ndarray         # (T,) int32 token-bucket capacity
    # ---- fault plane (engine-wide, replicated per shard) ----------------
    breaker: jnp.ndarray       # (3,) int32 [window W, threshold F, amp ceil];
    #                            F == 0 never trips, ceil == 0 never counts
    #                            amplification — faults still accumulate

    @classmethod
    def from_host(cls, t: EngineTables) -> "DeviceTables":
        """Move every host (numpy) table of ``t`` onto the default device
        unchanged in shape and dtype."""
        return cls(**{f: jnp.asarray(getattr(t, f)) for f in cls._fields})


class EngineState(NamedTuple):
    """The mutable half of one engine (or one shard): last values, the
    pending-SU queue, and the counters.  Per-tenant leaves have leading dim
    ``n_tenants``; the sharded engine stacks every leaf on a leading
    ``(n_shards,)`` axis and sums per-tenant leaves across shards on
    readback."""
    values: jnp.ndarray        # (N, C) last value per stream
    timestamps: jnp.ndarray    # (N,) int32 last emission ts (INT_MIN = never)
    q_sid: jnp.ndarray         # (Q,)
    q_vals: jnp.ndarray        # (Q, C)
    q_ts: jnp.ndarray          # (Q,)
    q_its: jnp.ndarray         # (Q,) ingest stamp (round of first ingest)
    q_seq: jnp.ndarray         # (Q,) FIFO tiebreaker
    q_valid: jnp.ndarray       # (Q,) bool
    seq: jnp.ndarray           # scalar int32
    tenant_emitted: jnp.ndarray  # (T,) emissions per owning tenant
    tokens: jnp.ndarray        # (T,) ingest token buckets (quota plane)
    tenant_queued: jnp.ndarray   # (T,) queue occupancy after the round
    tenant_dropped_quota: jnp.ndarray     # (T,) SUs shed over quota
    tenant_dropped_overflow: jnp.ndarray  # (T,) queue/exchange drops
    # ---- durability plane (sized by retention_slots / dlq_slots; both
    # default to 0, which keeps every leaf empty and every update a no-op) -
    ret_vals: jnp.ndarray      # (N, Rr, C) per-stream retained emissions
    ret_ts: jnp.ndarray        # (N, Rr) their timestamps
    ret_its: jnp.ndarray       # (N, Rr) their ingest stamps (replay keeps them)
    ret_count: jnp.ndarray     # (N,) emissions ever retained (ring cursor)
    dlq_sid: jnp.ndarray       # (D,) dead-letter stream ids
    dlq_vals: jnp.ndarray      # (D, C) dead-letter payloads
    dlq_ts: jnp.ndarray        # (D,) dead-letter timestamps
    dlq_its: jnp.ndarray       # (D,) dead-letter ingest stamps
    dlq_reason: jnp.ndarray    # (D,) drop class (see DLQ_REASONS)
    dlq_tenant: jnp.ndarray    # (D,) charged tenant
    dlq_fill: jnp.ndarray      # scalar int32 spool cursor
    # ---- fault-isolation plane (circuit breaker; always-on leaves) ------
    quarantined: jnp.ndarray   # (N,) bool — breaker-tripped rows (row may
    #                            still be `active`: quarantine is reversible
    #                            without re-admission)
    fault_count: jnp.ndarray   # (N,) int32 faults inside the current window
    fault_epoch: jnp.ndarray   # (N,) int32 round the current window opened
    fault_total: jnp.ndarray   # (N,) int32 lifetime faults (supervisor blame)
    round_idx: jnp.ndarray     # scalar int32 device round counter (windows)
    stats: Dict[str, jnp.ndarray]


class IngestBatch(NamedTuple):
    """One round's external Sensor Updates, padded to ``cfg.batch`` rows
    (``valid`` masks the live ones); ``ts`` are int32 event timestamps and
    ``its`` are int32 ingest stamps (the engine's global round counter at
    ``post()`` time — the latency plane's origin mark)."""
    sid: jnp.ndarray           # (B,)
    vals: jnp.ndarray          # (B, C)
    ts: jnp.ndarray            # (B,)
    valid: jnp.ndarray         # (B,) bool
    its: jnp.ndarray           # (B,) int32 ingest stamps


class SinkBatch(NamedTuple):
    """Per-round external emissions (push to MQTT/STOMP subscribers,
    model-plane bridge, ...).  ``its`` carries each record's original
    ingest stamp back to the host, so ingest->sink latency is read off the
    sink with zero extra device traffic (``StreamEngine.latency_records``)."""
    sid: jnp.ndarray           # (S,)
    vals: jnp.ndarray          # (S, C)
    ts: jnp.ndarray            # (S,)
    valid: jnp.ndarray         # (S,) bool
    its: jnp.ndarray           # (S,) int32 ingest stamps


class DeadLetter(NamedTuple):
    """One recovered drop, drained from the device dead-letter spool by
    ``StreamEngine.dead_letters()``: the SU's payload, the drop class
    (a :data:`DLQ_REASONS` name) and the tenant it was charged to.
    ``its`` preserves the SU's original ingest stamp so redelivery keeps
    the latency clock honest."""
    sid: int
    vals: np.ndarray
    ts: int
    reason: str
    tenant: int
    its: int = 0


STAT_KEYS = (
    "ingested", "ingest_stale", "ingest_coalesced",
    "processed", "discarded_stale", "filtered", "coalesced",
    "emitted", "enqueued", "dropped_overflow", "nonfinite",
    "dropped_revoked", "dropped_spool", "dropped_quota",
    "replayed",
    # queue-flow conservation counters (every SU that enters or leaves the
    # pending queue is counted exactly once):
    #   queued_in == popped + purged + current queue occupancy
    # holds at every host boundary — the invariant the elastic chaos soak
    # asserts across resizes.  "queued_in" counts successful enqueues
    # (ingest, stage-4 fan-out, replay/redelivery); "popped" counts SUs the
    # scheduler removed; "purged" counts SUs removed without being served
    # (revocation queue purges, resize scale-in overflow).
    "queued_in", "popped", "purged",
    # fault-isolation plane: SUs shed because their stream is quarantined
    # (breaker-tripped or host `quarantine()`), and dead letters whose
    # redelivery was refused because the stream is revoked/quarantined
    "dropped_poisoned", "redeliver_rejected",
    # stage-4 winners beyond a round's `sink_buffer`: stored (and
    # re-enqueued when subscribed) but absent from the external sink — no
    # dead letter, so no `dropped_` prefix
    "sink_overflow",
)

# Dead-letter drop classes: every ``dropped_*`` stat has a DLQ reason code,
# so a drained letter names which counter it was charged to.
DLQ_OVERFLOW, DLQ_REVOKED, DLQ_SPOOL, DLQ_QUOTA, DLQ_POISONED = range(5)
DLQ_REASONS = ("overflow", "revoked", "spool", "quota", "poisoned")


def init_state(cfg: EngineConfig) -> EngineState:
    """Fresh all-zero :class:`EngineState` for a single-device engine
    (timestamps at ``INT_MIN`` = never emitted, empty queue, zero counters
    and token buckets)."""
    N, C, Q, T = cfg.n_streams, cfg.channels, cfg.queue, cfg.n_tenants
    Rr, D = cfg.retention_slots, cfg.dlq_slots
    return EngineState(
        values=jnp.zeros((N, C), jnp.float32),
        timestamps=jnp.full((N,), INT_MIN, jnp.int32),
        q_sid=jnp.zeros((Q,), jnp.int32),
        q_vals=jnp.zeros((Q, C), jnp.float32),
        q_ts=jnp.zeros((Q,), jnp.int32),
        q_its=jnp.zeros((Q,), jnp.int32),
        q_seq=jnp.zeros((Q,), jnp.int32),
        q_valid=jnp.zeros((Q,), bool),
        seq=jnp.zeros((), jnp.int32),
        tenant_emitted=jnp.zeros((T,), jnp.int32),
        tokens=jnp.zeros((T,), jnp.int32),
        tenant_queued=jnp.zeros((T,), jnp.int32),
        tenant_dropped_quota=jnp.zeros((T,), jnp.int32),
        tenant_dropped_overflow=jnp.zeros((T,), jnp.int32),
        ret_vals=jnp.zeros((N, Rr, C), jnp.float32),
        ret_ts=jnp.zeros((N, Rr), jnp.int32),
        ret_its=jnp.zeros((N, Rr), jnp.int32),
        ret_count=jnp.zeros((N,), jnp.int32),
        dlq_sid=jnp.zeros((D,), jnp.int32),
        dlq_vals=jnp.zeros((D, C), jnp.float32),
        dlq_ts=jnp.zeros((D,), jnp.int32),
        dlq_its=jnp.zeros((D,), jnp.int32),
        dlq_reason=jnp.zeros((D,), jnp.int32),
        dlq_tenant=jnp.zeros((D,), jnp.int32),
        dlq_fill=jnp.zeros((), jnp.int32),
        quarantined=jnp.zeros((N,), bool),
        fault_count=jnp.zeros((N,), jnp.int32),
        fault_epoch=jnp.zeros((N,), jnp.int32),
        fault_total=jnp.zeros((N,), jnp.int32),
        round_idx=jnp.zeros((), jnp.int32),
        stats={k: jnp.zeros((), jnp.int32) for k in STAT_KEYS},
    )


def dlq_append(state: EngineState, sid, vals, ts, tenant, reason: int, mask,
               its=None) -> EngineState:
    """Spill the masked dropped SUs into the dead-letter spool: payload +
    timestamp + charged tenant + drop-class ``reason`` (a ``DLQ_*`` code),
    appended behind ``dlq_fill``.  The spool saturates — letters beyond
    ``cfg.dlq_slots`` are lost (the ``dropped_*`` stats still count them) —
    and with ``dlq_slots == 0`` this is a Python-level no-op, so the DLQ
    costs nothing when off.  ``tenant=None`` records the sentinel ``-1``
    (owner unknown at the drop site) rather than charging tenant 0;
    ``its=None`` records stamp 0 (drop sites that predate the latency
    plane)."""
    D = state.dlq_sid.shape[0]
    if D == 0:
        return state
    if tenant is None:
        tenant = jnp.full_like(sid, -1)
    if its is None:
        its = jnp.zeros_like(sid)
    rank = state.dlq_fill + jnp.cumsum(mask.astype(jnp.int32)) - 1
    dest = jnp.where(mask & (rank < D), rank, D)
    return state._replace(
        dlq_sid=state.dlq_sid.at[dest].set(sid, mode="drop"),
        dlq_vals=state.dlq_vals.at[dest].set(vals, mode="drop"),
        dlq_ts=state.dlq_ts.at[dest].set(ts, mode="drop"),
        dlq_its=state.dlq_its.at[dest].set(its, mode="drop"),
        dlq_reason=state.dlq_reason.at[dest].set(reason, mode="drop"),
        dlq_tenant=state.dlq_tenant.at[dest].set(tenant, mode="drop"),
        dlq_fill=jnp.minimum(state.dlq_fill + mask.sum(dtype=jnp.int32), D),
    )


# --------------------------------------------------------------------------
# queue helpers
# --------------------------------------------------------------------------

# _first_free implementation cutover: the X-step selection loop costs
# X * O(Q) while the nonzero scatter costs one O(Q) pass with a ~80x
# larger per-element constant (XLA CPU scatter), so selection wins for
# small request widths (phase-0 ingest: X = batch) and loses for wide
# ones (stage-4 re-enqueue: X = work = batch * max_out).
_FREE_SCAN_MAX = 64


def _first_free(q_valid: jnp.ndarray, X: int, fast: bool = False
                ) -> jnp.ndarray:
    """Indices of the first ``X`` free queue slots, ascending, padded
    with ``Q`` — ``jnp.nonzero(~q_valid, size=X, fill_value=Q)[0]``
    bit-exactly.  For ``X <= _FREE_SCAN_MAX`` it runs as ``X``
    vectorized argmin steps (the packed scheduler pop's selection
    idiom, ~10x cheaper than the full-queue scatter ``nonzero`` lowers
    to); wider requests keep the scatter, which is flat in ``X``.
    ``fast=True`` (the fused round) switches to the cumsum+searchsorted
    search of :mod:`repro.kernels.round_fuse` — still bit-exact, one
    O(Q log X) pass regardless of width."""
    if fast:
        from repro.kernels.round_fuse.ref import first_free_slots
        return first_free_slots(q_valid, X)
    Q = q_valid.shape[0]
    if X > _FREE_SCAN_MAX:
        return jnp.nonzero(~q_valid, size=X, fill_value=Q)[0]
    val0 = jnp.where(~q_valid, jnp.arange(Q, dtype=jnp.int32), Q)

    def step(k, carry):
        out, val = carry
        m = jnp.min(val)
        return out.at[k].set(m), jnp.where(val == m, Q, val)

    out, _ = jax.lax.fori_loop(
        0, X, step, (jnp.full((X,), Q, jnp.int32), val0))
    return out


def _enqueue(state: EngineState, sid, vals, ts, mask, tenant=None,
             fast_free: bool = False, its=None
             ) -> Tuple[EngineState, jnp.ndarray]:
    """Append masked items into free queue slots; returns #dropped.  With
    ``tenant`` (an (X,) tenant id per item), overflow drops are also
    charged to ``state.tenant_dropped_overflow`` so contention for queue
    slots is attributable per tenant.  ``its`` (an (X,) ingest stamp per
    item, default zeros) rides along in ``q_its`` — the latency plane.

    Sequence numbers advance *on accept*: a dropped item consumes no
    ``state.seq`` ticket, so a later redelivery of a dead-lettered SU
    receives a fresh (higher) sequence number rather than leaving a
    permanent hole — the FIFO tie-break order stays dense (the ordering
    contract is documented in docs/OPERATIONS.md)."""
    Q = state.q_valid.shape[0]
    X = sid.shape[0]
    if its is None:
        its = jnp.zeros_like(sid)
    free = _first_free(state.q_valid, X, fast_free)              # first X free
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1               # slot per item
    dest = jnp.where(mask, free[jnp.clip(rank, 0, X - 1)], Q)   # Q -> dropped
    ok = mask & (dest < Q)
    dest = jnp.where(ok, dest, Q)
    seq_nos = state.seq + jnp.cumsum(ok.astype(jnp.int32))
    new = state._replace(
        q_sid=state.q_sid.at[dest].set(sid, mode="drop"),
        q_vals=state.q_vals.at[dest].set(vals, mode="drop"),
        q_ts=state.q_ts.at[dest].set(ts, mode="drop"),
        q_its=state.q_its.at[dest].set(its, mode="drop"),
        q_seq=state.q_seq.at[dest].set(seq_nos, mode="drop"),
        q_valid=state.q_valid.at[dest].set(True, mode="drop"),
        seq=state.seq + ok.sum(dtype=jnp.int32),
    )
    drop_mask = mask & ~ok
    if tenant is not None:
        # negative ids are the "unknown owner" sentinel — chargeable to no
        # tenant, and .at[] would *wrap* them (mode="drop" only drops
        # indices beyond the dim), so they must be routed to the pad row
        T = state.tenant_dropped_overflow.shape[0]
        new = new._replace(
            tenant_dropped_overflow=new.tenant_dropped_overflow.at[
                jnp.where(drop_mask & (tenant >= 0), tenant, T)
            ].add(1, mode="drop"))
    new = dlq_append(new, sid, vals, ts, tenant, DLQ_OVERFLOW, drop_mask,
                     its=its)
    return new, drop_mask.sum(dtype=jnp.int32)


def _tenant_rank(mask: jnp.ndarray, tenant_idx: jnp.ndarray,
                 n_tenants: int) -> jnp.ndarray:
    """0-based rank of each masked item among *masked items of the same
    tenant*, in array order — the shared idiom of the weighted-fair pop
    (ranks within the (priority, seq)-sorted queue) and the quota gate
    (arrival number within the ingest batch).  Unmasked lanes read an
    arbitrary value; callers gate on ``mask``."""
    onehot = mask[:, None] & \
        (tenant_idx[:, None] == jnp.arange(n_tenants)[None, :])
    return jnp.take_along_axis(
        jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1,
        tenant_idx[:, None], axis=1)[:, 0]


def _pop(state: EngineState, priority_by_sid: jnp.ndarray, batch: int,
         tenant_by_sid: Optional[jnp.ndarray] = None,
         weight: Optional[jnp.ndarray] = None,
         scheduler: str = "packed"):
    """Pop up to ``batch`` queued SUs, lowest sort key first.

    Without QoS args this is the §IV-E priority pop: lowest ``(priority,
    seq)`` wins (priority table all-zero == plain FIFO).  With
    ``tenant_by_sid`` (id space of ``q_sid``) and a per-tenant ``weight``
    table, the key generalizes to weighted-fair queueing composed with the
    per-sid priority: within each tenant, queued SUs are ranked by
    ``(priority, seq)``; a tenant of weight ``w > 0`` gives its rank-k SU
    the virtual tag ``k * FAIR_SCALE // w``, and the global order is
    ``(priority, virtual tag, seq)``.  Backlogged tenants in the same
    priority class are therefore served proportionally to their weights,
    and every tenant's head SU carries tag 0 — so while a weighted tenant
    waits, every pop slot goes to a strictly *older* SU, which bounds any
    tenant's wait by ``ceil(older_backlog / batch)`` rounds: starvation-
    free regardless of the weight assignment (tests/test_qos.py holds the
    pop to this against a brute-force oracle).  Weight 0 (the default)
    exempts a tenant: its tags are all 0, and an all-zero weight table
    reproduces the pre-QoS pop bit-exactly.

    ``scheduler`` selects the implementation — identical results, very
    different cost:

    * ``"packed"`` (the default): selection pop.  Per-slot key planes are
      built once, then the ``batch`` winners are extracted by repeated
      vectorized lexicographic argmin with the fair tag maintained
      incrementally (:mod:`repro.kernels.sched_pop` — fused Pallas kernel
      on TPU, pure-jnp ref elsewhere).  O(Q·batch), no sort.
    * ``"lexsort"``: the reference two-full-queue-sort pop, O(Q log Q) —
      kept as the oracle the differential suite pins ``"packed"`` to.

    ``priority_by_sid``/``tenant_by_sid`` are indexed by whatever id space
    ``q_sid`` uses (global sids in the sharded engine, table rows on a
    single device).  Returns ``(state, (sid, vals, ts, its, valid))`` —
    ``its`` is each popped SU's ingest stamp (the latency plane)."""
    if scheduler == "packed":
        from repro.kernels.sched_pop.ops import sched_pop
        prio_slot = priority_by_sid[state.q_sid]
        if tenant_by_sid is None:
            t_slot = jnp.zeros_like(state.q_sid)
            w_slot = jnp.zeros_like(state.q_sid)
        else:
            T = weight.shape[0]
            t_slot = jnp.clip(tenant_by_sid[state.q_sid], 0, T - 1)
            w_slot = weight[t_slot]
        take, popped = sched_pop(prio_slot, state.q_seq, state.q_valid,
                                 t_slot, w_slot, state.q_sid, state.q_vals,
                                 state.q_ts, batch)
        p_sid, p_vals, p_ts, p_valid = popped
        popped = (p_sid, p_vals, p_ts, state.q_its[take], p_valid)
        return state._replace(
            q_valid=state.q_valid.at[take].set(False)), popped
    key = jnp.where(state.q_valid, priority_by_sid[state.q_sid], INT_MAX)
    if tenant_by_sid is None:
        order = jnp.lexsort((state.q_seq, key))
    else:
        T = weight.shape[0]
        order0 = jnp.lexsort((state.q_seq, key))     # (priority, seq) order
        t_sort = jnp.clip(tenant_by_sid[state.q_sid], 0, T - 1)[order0]
        v_sort = state.q_valid[order0]
        rank = _tenant_rank(v_sort, t_sort, T)       # within-tenant rank
        w = weight[t_sort]
        rank = jnp.minimum(rank, RANK_LIM)           # int32-safe tags
        vtag = jnp.where(v_sort & (w > 0), rank * FAIR_SCALE // w, 0)
        reorder = jnp.lexsort((state.q_seq[order0], vtag, key[order0]))
        order = order0[reorder]
    take = order[:batch]
    pvalid = state.q_valid[take]
    popped = (state.q_sid[take], state.q_vals[take], state.q_ts[take],
              state.q_its[take], pvalid)
    state = state._replace(q_valid=state.q_valid.at[take].set(False))
    return state, popped


# --------------------------------------------------------------------------
# phase 0 / stage 4 — shared by the single-device and sharded steps
# --------------------------------------------------------------------------

def ingest_phase(state: EngineState, stats: Dict[str, jnp.ndarray],
                 ingest: IngestBatch,
                 row: jnp.ndarray,          # (B,) rows into values/timestamps
                 q_sid: jnp.ndarray,        # (B,) ids to enqueue (global sids)
                 active: jnp.ndarray,       # (B,) row active mask
                 n_rows: int,
                 tenant_of_row: Optional[jnp.ndarray] = None,  # (B,)
                 quota: Optional[jnp.ndarray] = None,          # (T,)
                 burst: Optional[jnp.ndarray] = None,          # (T,)
                 fast_free: bool = False,
                 quarantined: Optional[jnp.ndarray] = None,    # (B,) row mask
                 ) -> Tuple[EngineState, Dict[str, jnp.ndarray]]:
    """Phase 0: admit external SUs — store last-value/timestamp, enqueue for
    dispatch.  On a single device ``row == q_sid == sid``; the sharded step
    stores to shard-local rows but queues global sids.  SUs addressed to
    revoked rows are dropped into ``dropped_revoked``; SUs addressed to
    active-but-quarantined rows (breaker tripped, or host ``quarantine()``)
    are dropped into ``dropped_poisoned`` and dead-lettered as ``poisoned``
    so ``unquarantine`` + ``redeliver`` can bring them back.

    With the QoS args, per-tenant ingest quotas are enforced first: each
    tenant's token bucket refills by ``quota[t]`` tokens per round up to
    ``burst[t]``, every arriving SU (valid, active row) consumes one
    token, and arrivals beyond the bucket are *shed* — counted in
    ``stats["dropped_quota"]`` and ``state.tenant_dropped_quota[t]``, and
    neither stored nor enqueued, so an over-quota tenant cannot crowd the
    queue.  ``quota[t] == 0`` (the default) means unlimited — the
    pre-quota behavior bit-exactly."""
    if quarantined is None:
        quarantined = jnp.zeros_like(active)
    arrive = ingest.valid & active & ~quarantined
    if tenant_of_row is None:
        i_live = arrive
    else:
        T = quota.shape[0]
        t_of = jnp.clip(tenant_of_row, 0, T - 1)
        tokens = jnp.minimum(state.tokens + quota, burst)  # per-round refill
        arrival_no = _tenant_rank(arrive, t_of, T)  # rank among same-tenant
        in_quota = (quota[t_of] == 0) | (arrival_no < tokens[t_of])
        shed = arrive & ~in_quota
        i_live = arrive & in_quota
        spent = jnp.zeros((T,), jnp.int32).at[t_of].add(
            (arrive & in_quota).astype(jnp.int32))
        state = state._replace(
            tokens=jnp.where(quota > 0, tokens - spent, tokens),
            tenant_dropped_quota=state.tenant_dropped_quota.at[
                jnp.where(shed, t_of, T)].add(1, mode="drop"))
        stats["dropped_quota"] += shed.sum(dtype=jnp.int32)
        state = dlq_append(state, q_sid, ingest.vals, ingest.ts, t_of,
                           DLQ_QUOTA, shed, its=ingest.its)
    i_keep = i_live & (ingest.ts > state.timestamps[row])
    i_win = consistency.resolve_winners(row, ingest.ts, i_keep, n_rows)
    i_dest = jnp.where(i_win, row, n_rows)
    state = state._replace(
        values=state.values.at[i_dest].set(ingest.vals, mode="drop"),
        timestamps=state.timestamps.at[i_dest].set(ingest.ts, mode="drop"),
    )
    Rr = state.ret_ts.shape[-1]     # static: retention ring width
    if Rr:                          # a source's stored SU is its emission
        slot = state.ret_count[row] % Rr
        state = state._replace(
            ret_vals=state.ret_vals.at[i_dest, slot].set(
                ingest.vals, mode="drop"),
            ret_ts=state.ret_ts.at[i_dest, slot].set(
                ingest.ts, mode="drop"),
            ret_its=state.ret_its.at[i_dest, slot].set(
                ingest.its, mode="drop"),
            ret_count=state.ret_count.at[i_dest].add(1, mode="drop"))
    stats["ingested"] += ingest.valid.sum(dtype=jnp.int32)
    stats["dropped_revoked"] += (ingest.valid & ~active).sum(dtype=jnp.int32)
    state = dlq_append(state, q_sid, ingest.vals, ingest.ts, tenant_of_row,
                       DLQ_REVOKED, ingest.valid & ~active, its=ingest.its)
    i_poison = ingest.valid & active & quarantined
    stats["dropped_poisoned"] += i_poison.sum(dtype=jnp.int32)
    state = dlq_append(state, q_sid, ingest.vals, ingest.ts, tenant_of_row,
                       DLQ_POISONED, i_poison, its=ingest.its)
    stats["ingest_stale"] += (i_live & ~i_keep).sum(dtype=jnp.int32)
    stats["ingest_coalesced"] += (i_keep & ~i_win).sum(dtype=jnp.int32)
    state, dropped = _enqueue(state, q_sid, ingest.vals, ingest.ts, i_win,
                              tenant_of_row, fast_free, its=ingest.its)
    stats["dropped_overflow"] += dropped
    stats["queued_in"] += i_win.sum(dtype=jnp.int32) - dropped
    return state, stats


def store_and_emit(cfg: EngineConfig, tables: DeviceTables,
                   state: EngineState, stats: Dict[str, jnp.ndarray],
                   rows: jnp.ndarray,       # (W,) target rows (in-range)
                   emit_sid: jnp.ndarray,   # (W,) target ids for queue/sink
                   order: jnp.ndarray,      # (W,) coalescing tie key (trigger)
                   new_vals: jnp.ndarray, ts_out: jnp.ndarray,
                   keep: jnp.ndarray, n_rows: int,
                   fast_free: bool = False,
                   wi_its: Optional[jnp.ndarray] = None,
                   ) -> Tuple[EngineState, Dict[str, jnp.ndarray], SinkBatch]:
    """Stage 4: coalesce winners, store them, account per-tenant emissions,
    re-enqueue winners that have subscribers, and fill the external sink
    buffer.  ``rows`` index this engine's state slice (== ``emit_sid`` on a
    single device; shard-local rows in the sharded step).  ``wi_its``
    ((W,) per-item ingest stamps, default zeros) is carried unchanged into
    the retention ring, the fan-out re-enqueue and the sink buffer — the
    latency plane's device-side thread."""
    S, C = cfg.sink_buffer, cfg.channels
    if wi_its is None:
        wi_its = jnp.zeros_like(emit_sid)
    win = consistency.resolve_winners(rows, ts_out, keep, n_rows, order=order)
    stats["coalesced"] += (keep & ~win).sum(dtype=jnp.int32)
    stats["emitted"] += win.sum(dtype=jnp.int32)
    dest = jnp.where(win, rows, n_rows)
    state = state._replace(
        values=state.values.at[dest].set(new_vals, mode="drop"),
        timestamps=state.timestamps.at[dest].set(ts_out, mode="drop"),
        tenant_emitted=state.tenant_emitted.at[
            jnp.where(win, tables.tenant[rows], cfg.n_tenants)
        ].add(1, mode="drop"),
    )

    # per-stream retention ring: each winner also lands in its row's ring
    # at cursor `ret_count % Rr` (at most one winner per row per round, so
    # the scatter indices are unique).  Off (Rr == 0) costs nothing.
    Rr = cfg.retention_slots
    if Rr:
        slot = state.ret_count[rows] % Rr
        state = state._replace(
            ret_vals=state.ret_vals.at[dest, slot].set(new_vals, mode="drop"),
            ret_ts=state.ret_ts.at[dest, slot].set(ts_out, mode="drop"),
            ret_its=state.ret_its.at[dest, slot].set(wi_its, mode="drop"),
            ret_count=state.ret_count.at[dest].add(1, mode="drop"),
        )

    # re-dispatch winners that themselves have subscribers (queue drops
    # charged to the emitting stream's owner tenant)
    fanout_more = win & (tables.out_count[rows] > 0)
    state, dropped = _enqueue(state, emit_sid, new_vals, ts_out, fanout_more,
                              tables.tenant[rows], fast_free, its=wi_its)
    stats["dropped_overflow"] += dropped
    stats["enqueued"] += fanout_more.sum(dtype=jnp.int32)
    stats["queued_in"] += fanout_more.sum(dtype=jnp.int32) - dropped

    # external sink buffer: first `sink_buffer` winners this round; the
    # rest are counted, not delivered
    sink_rank = jnp.cumsum(win.astype(jnp.int32)) - 1
    stats["sink_overflow"] += (win & (sink_rank >= S)).sum(dtype=jnp.int32)
    sdest = jnp.where(win & (sink_rank < S), sink_rank, S)
    sink = SinkBatch(
        sid=jnp.zeros((S,), jnp.int32).at[sdest].set(emit_sid, mode="drop"),
        vals=jnp.zeros((S, C), jnp.float32).at[sdest].set(new_vals,
                                                          mode="drop"),
        ts=jnp.zeros((S,), jnp.int32).at[sdest].set(ts_out, mode="drop"),
        valid=jnp.zeros((S,), bool).at[sdest].set(True, mode="drop"),
        its=jnp.zeros((S,), jnp.int32).at[sdest].set(wi_its, mode="drop"),
    )
    return state, stats, sink


def tenant_occupancy(state: EngineState, tenant_by_sid: jnp.ndarray,
                     n_tenants: int) -> jnp.ndarray:
    """Per-tenant pending-SU queue occupancy — the backpressure signal
    surfaced to the host in ``state.tenant_queued`` after every round.
    ``tenant_by_sid`` is indexed by ``q_sid``'s id space (like ``_pop``).
    Computed as a one-hot reduction rather than a scatter-add: same sums,
    no O(Q) serial scatter on the per-round hot path."""
    q_t = jnp.clip(tenant_by_sid[state.q_sid], 0, n_tenants - 1)
    onehot = (q_t[:, None] == jnp.arange(n_tenants)[None, :]) \
        & state.q_valid[:, None]
    return onehot.sum(axis=0, dtype=jnp.int32)


# --------------------------------------------------------------------------
# fault-isolation plane — shared by the fused, staged and sharded rounds
# --------------------------------------------------------------------------

def fault_events(breaker: jnp.ndarray,
                 badf: jnp.ndarray,        # (W,) non-finite VM results
                 wi_valid: jnp.ndarray,    # (W,) live work-item lanes
                 t_row: jnp.ndarray,       # (W,) target row per lane
                 fan: jnp.ndarray,         # (B,) valid fan-out per event
                 e_valid: jnp.ndarray,     # (B,) live popped events
                 e_row: jnp.ndarray,       # (B,) source row per event
                 n_rows: int) -> jnp.ndarray:
    """Fold one round's two fault classes into a per-row event mask:

    * **non-finite** — a program produced NaN/Inf this round, charged to
      the *target* row that ran the bytecode (``badf`` is pre-masked VM
      output; lanes are gated by ``wi_valid`` exactly like the
      ``nonfinite`` stat, so counts and faults always agree);
    * **amplification** — a popped SU fanned out to more than
      ``breaker[2]`` valid work items, charged to the *source* row whose
      out-degree did it (ceiling 0 disables the class).

    Both scatters are any-reductions: a row faults at most once per round
    no matter how many lanes misbehaved, which is what makes the window
    counters path-independent (fused == staged == sharded)."""
    nf_row = jnp.zeros((n_rows,), bool).at[
        jnp.where(badf & wi_valid, t_row, n_rows)].set(True, mode="drop")
    amp = (breaker[2] > 0) & e_valid & (fan > breaker[2])
    amp_row = jnp.zeros((n_rows,), bool).at[
        jnp.where(amp, e_row, n_rows)].set(True, mode="drop")
    return nf_row | amp_row


def fault_phase(state: EngineState, stats: Dict[str, jnp.ndarray],
                breaker: jnp.ndarray,       # (3,) int32 [W, F, amp ceiling]
                fault_evt: jnp.ndarray,     # (N,) per-row fault events
                active: jnp.ndarray,        # (N,) real active mask
                tenant_of_row: jnp.ndarray,  # (N,) owning tenant per row
                q_row: jnp.ndarray,         # (Q,) row per queue slot
                ) -> Tuple[EngineState, Dict[str, jnp.ndarray]]:
    """Advance the per-stream circuit breaker one round and quarantine the
    rows that tripped — all runtime data, traced once.

    Window state machine (per row): the first fault opens a W-round window
    anchored at ``fault_epoch``; further faults inside it increment
    ``fault_count``; a fault after expiry restarts the window at 1; a
    fault-free round past expiry decays the count to 0.  When an active,
    not-yet-quarantined row reaches ``count >= F`` (F > 0) it trips:
    ``quarantined`` flips on device and every queued SU of that row is
    purged to the DLQ as ``poisoned`` this same round (later arrivals are
    shed at the ingest gate).  ``fault_total`` accumulates forever — the
    supervisor's blame signal — and ``round_idx`` is the window clock."""
    W, F = breaker[0], breaker[1]
    rid = state.round_idx
    in_win = (rid - state.fault_epoch) < W
    restart = fault_evt & (~in_win | (state.fault_count == 0))
    count = jnp.where(
        fault_evt,
        jnp.where(restart, 1, state.fault_count + 1),
        jnp.where(in_win, state.fault_count, 0)).astype(jnp.int32)
    epoch = jnp.where(restart, rid, state.fault_epoch)
    trip = (F > 0) & (count >= F) & active & ~state.quarantined
    quarantined = state.quarantined | trip
    state = state._replace(
        quarantined=quarantined,
        fault_count=count,
        fault_epoch=epoch,
        fault_total=state.fault_total + fault_evt.astype(jnp.int32),
        round_idx=rid + 1,
    )
    # purge queued SUs of quarantined rows (idempotent: hit slots go
    # invalid, and the ingest/pop gates keep new ones out while tripped)
    hit = state.q_valid & quarantined[q_row]
    n_hit = hit.sum(dtype=jnp.int32)
    stats["dropped_poisoned"] += n_hit
    stats["purged"] += n_hit
    state = dlq_append(state, state.q_sid, state.q_vals, state.q_ts,
                       tenant_of_row[q_row], DLQ_POISONED, hit,
                       its=state.q_its)
    return state._replace(q_valid=state.q_valid & ~hit), stats


# --------------------------------------------------------------------------
# stage 1 — subscriber dispatching (jnp reference; Pallas kernel optional)
# --------------------------------------------------------------------------

def fanout_reference(
    sid: jnp.ndarray,        # (B,)
    ts: jnp.ndarray,         # (B,)
    pvalid: jnp.ndarray,     # (B,)
    out_table: jnp.ndarray,  # (N, F)
    timestamps: jnp.ndarray, # (N,)
    *,
    with_early: bool = True,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Expand each event to its subscribers; optionally also the early
    stale-check against the targets' last-emission timestamps (saves
    fetching for obvious discards).  Returns targets (B, F) and the
    early-keep mask (B, F), or ``None`` in its place when the caller
    applies the equivalent check later (``with_early=False`` — the engine
    does, in ``process_work_items``' keep_mask, so requesting no mask
    skips the timestamp gather entirely)."""
    targets = out_table[jnp.clip(sid, 0, out_table.shape[0] - 1)]
    tvalid = (targets >= 0) & pvalid[:, None]
    if not with_early:
        return jnp.where(tvalid, targets, -1), None
    t_safe = jnp.clip(targets, 0, timestamps.shape[0] - 1)
    early = tvalid & (ts[:, None] > timestamps[t_safe])
    return jnp.where(tvalid, targets, -1), early


# --------------------------------------------------------------------------
# stages 2 + 3 — shared by the single-device and sharded engines
# --------------------------------------------------------------------------

def process_work_items(
    cfg: EngineConfig,
    tables: DeviceTables,
    rows: jnp.ndarray,            # (W,) row into tables.* (clipped, in-range)
    t_sid: jnp.ndarray,           # (W,) target id in values_by_sid's space
    wi_src: jnp.ndarray,          # (W,) triggering stream id
    wi_vals: jnp.ndarray,         # (W, C) triggering SU payload
    wi_ts: jnp.ndarray,           # (W,) triggering SU timestamp
    wi_valid: jnp.ndarray,        # (W,) bool
    values_by_sid: jnp.ndarray,   # (N, C) last values, indexed like t_sid
    timestamps_by_sid: jnp.ndarray,  # (N,)
):
    """Data fetching + transformation/filtering for a work-item batch.

    On a single device ``rows == t_sid`` index the global tables/state; the
    sharded engine passes shard-local table rows plus the all-gathered
    by-sid value/timestamp snapshot, so both engines evaluate identical
    Listing-2 semantics.  Returns ``(new_vals, ts_out, live, keep, counts,
    badf)`` where counts holds the stage-3 stat increments and ``badf``
    flags work items whose VM result was non-finite (pre-``wi_valid`` —
    mask it like the ``nonfinite`` count does) for the fault plane.
    """
    W = t_sid.shape[0]
    M, C, R = cfg.max_in, cfg.channels, cfg.n_regs
    n_sid = timestamps_by_sid.shape[0]

    # ---- stage 2: data fetching (lock-free gathers) ----------------------
    in_row = tables.in_table[rows]                   # (W, M)
    in_valid = in_row >= 0
    src_safe = jnp.clip(in_row, 0, n_sid - 1)
    vals_in = values_by_sid[src_safe]                # (W, M, C)
    ts_in = jnp.where(in_valid, timestamps_by_sid[src_safe], INT_MIN)
    trig = jnp.argmax((in_row == wi_src[:, None]) & in_valid, axis=1)
    widx = jnp.arange(W)
    vals_in = vals_in.at[widx, trig].set(wi_vals)    # fresh SU overrides
    ts_in = ts_in.at[widx, trig].set(wi_ts)
    prev_vals = values_by_sid[t_sid]
    prev_ts = timestamps_by_sid[t_sid]

    # ---- stage 3: transformation & filtering -----------------------------
    regs = jnp.zeros((W, R), jnp.float32)
    flat_in = jnp.where(in_valid[..., None], vals_in, 0.0).reshape(W, M * C)
    regs = regs.at[:, cfg.reg_inputs:cfg.reg_inputs + M * C].set(flat_in)
    regs = regs.at[:, cfg.reg_prev:cfg.reg_prev + C].set(prev_vals)
    regs = regs.at[:, cfg.reg_ts].set(wi_ts.astype(jnp.float32))
    regs = regs.at[:, cfg.reg_trigger].set(trig.astype(jnp.float32))
    regs_out = pvm.execute_batch(tables.progs[rows], tables.consts[rows], regs)
    new_vals = regs_out[:, cfg.reg_result:cfg.reg_result + C]
    finite = jnp.isfinite(new_vals)
    new_vals = jnp.where(finite, new_vals, 0.0)
    pref = regs_out[:, cfg.reg_pref] != 0.0
    postf = regs_out[:, cfg.reg_postf] != 0.0

    keep_ts = consistency.keep_mask(wi_ts, prev_ts)
    ts_out = consistency.output_timestamp(wi_ts, prev_ts, ts_in, in_valid)
    live = wi_valid & tables.is_composite[rows] & tables.active[rows]
    keep = live & keep_ts & pref & postf
    counts = {
        "processed": live.sum(dtype=jnp.int32),
        "discarded_stale": (live & ~keep_ts).sum(dtype=jnp.int32),
        "filtered": (live & keep_ts & ~(pref & postf)).sum(dtype=jnp.int32),
        "nonfinite": ((~finite).any(axis=-1) & wi_valid).sum(dtype=jnp.int32),
    }
    return new_vals, ts_out, live, keep, counts, (~finite).any(axis=-1)


# --------------------------------------------------------------------------
# the step
# --------------------------------------------------------------------------

def make_step(
    cfg: EngineConfig,
    fanout_fn: Callable = fanout_reference,
    donate: bool = True,
    jit: bool = True,
    fused: Optional[bool] = None,
) -> Callable:
    """Build the jitted engine round.  ``fanout_fn`` may be swapped for the
    Pallas `stream_dispatch` kernel; both compute stage 1.  ``jit=False``
    returns the raw step (the dry-run jits it with explicit shardings).

    ``fused`` selects the round-fusion plane (default:
    ``cfg.fused_round``): stages 1-3 run as one
    :func:`repro.kernels.round_fuse.ops.fused_stages` operation — a single
    Pallas megakernel on TPU — instead of the staged pop / ``fanout_fn`` /
    ``process_work_items`` sequence.  Bit-identical for fusable programs;
    the host engine falls back to the staged step otherwise
    (``StreamEngine`` checks fusability at every program edit).  The fused
    pop *is* the packed scheduler, so ``scheduler="lexsort"`` always takes
    the staged path."""
    N, C, F = cfg.n_streams, cfg.channels, cfg.max_out
    B, W = cfg.batch, cfg.work
    if fused is None:
        fused = cfg.fused_round
    fused = fused and cfg.scheduler == "packed"

    if fused:
        from repro.kernels.round_fuse.ops import fused_stages
        from repro.kernels.round_fuse.ref import RegLayout
        layout = RegLayout.from_cfg(cfg)
        T = cfg.n_tenants

        def step(tables: DeviceTables, state: EngineState,
                 ingest: IngestBatch) -> Tuple[EngineState, SinkBatch]:
            stats = dict(state.stats)

            # ---- phase 0: ingest external SUs ---------------------------
            with jax.named_scope("ingest"):
                i_sid = jnp.clip(ingest.sid, 0, N - 1)
                state, stats = ingest_phase(
                    state, stats, ingest, i_sid, i_sid,
                    tables.active[i_sid], N, tables.tenant[i_sid],
                    tables.quota, tables.burst, fast_free=True,
                    quarantined=state.quarantined[i_sid])

            # ---- stages 1-3 fused: pop, fan-out, fetch+VM, window gate --
            # quarantined rows ride the kernel's existing active gate (no
            # signature change): the *effective* mask keeps them from
            # dispatching or winning; the real mask is re-read outside so
            # revoked and poisoned drops stay separately accounted
            with jax.named_scope("round_fuse"):
                eff_active = tables.active & ~state.quarantined
                prio_slot = tables.priority[state.q_sid]
                t_slot = jnp.clip(tables.tenant[state.q_sid], 0, T - 1)
                w_slot = tables.weight[t_slot]
                take, (e_sid, e_vals, e_ts, e_pop, e_act), wi_t, applied = \
                    fused_stages(prio_slot, state.q_seq, state.q_valid,
                                 t_slot, w_slot, state.q_sid, state.q_vals,
                                 state.q_ts, B, tables.out_table,
                                 tables.in_table, tables.progs,
                                 tables.consts, tables.is_composite,
                                 eff_active, state.values,
                                 state.timestamps, layout)
            with jax.named_scope("pop_accounting"):
                # the ingest stamps of the popped slots ride outside the
                # kernel: `take` is the same slot selection the staged
                # _pop returns, so this gather keeps the two paths
                # bit-identical
                e_its = state.q_its[take]
                state = state._replace(
                    q_valid=state.q_valid.at[take].set(False))
                stats["popped"] += e_pop.sum(dtype=jnp.int32)
                # events whose stream was revoked/quarantined while queued
                # drop here (split so triage can tell a torn-down tenant
                # from a breaker-tripped one)
                e_row = jnp.clip(e_sid, 0, N - 1)
                e_real = tables.active[e_row]
                e_poison = e_pop & e_real & state.quarantined[e_row]
                stats["dropped_revoked"] += \
                    (e_pop & ~e_real).sum(dtype=jnp.int32)
                state = dlq_append(state, e_sid, e_vals, e_ts,
                                   tables.tenant[e_row],
                                   DLQ_REVOKED, e_pop & ~e_real, its=e_its)
                stats["dropped_poisoned"] += e_poison.sum(dtype=jnp.int32)
                state = dlq_append(state, e_sid, e_vals, e_ts,
                                   tables.tenant[e_row],
                                   DLQ_POISONED, e_poison, its=e_its)
                new_vals, ts_out, live, keep, keep_ts, passf, badf = applied
                stats["processed"] += live.sum(dtype=jnp.int32)
                stats["discarded_stale"] += \
                    (live & ~keep_ts).sum(dtype=jnp.int32)
                stats["filtered"] += \
                    (live & keep_ts & ~passf).sum(dtype=jnp.int32)
                stats["nonfinite"] += (badf & (wi_t >= 0)).sum(dtype=jnp.int32)

            # ---- stage 4: store, trigger actions and emit ---------------
            with jax.named_scope("store_emit"):
                t = jnp.clip(wi_t, 0, N - 1)
                wi_src = jnp.repeat(e_sid, F)
                wi_its = jnp.repeat(e_its, F)
                state, stats, sink = store_and_emit(
                    cfg, tables, state, stats, t, t, wi_src, new_vals,
                    ts_out, keep, N, fast_free=True, wi_its=wi_its)

            # ---- fault plane: breaker window + device auto-quarantine ---
            with jax.named_scope("fault"):
                fan = (wi_t.reshape(B, F) >= 0).sum(axis=1, dtype=jnp.int32)
                fault_evt = fault_events(tables.breaker, badf, wi_t >= 0, t,
                                         fan, e_pop & e_act, e_row, N)
                state, stats = fault_phase(
                    state, stats, tables.breaker, fault_evt, tables.active,
                    tables.tenant, jnp.clip(state.q_sid, 0, N - 1))
                state = state._replace(
                    stats=stats,
                    tenant_queued=tenant_occupancy(state, tables.tenant,
                                                   cfg.n_tenants))
            return state, sink

        if not jit:
            return step
        return jax.jit(step, donate_argnums=(1,) if donate else ())

    def step(tables: DeviceTables, state: EngineState, ingest: IngestBatch
             ) -> Tuple[EngineState, SinkBatch]:
        stats = dict(state.stats)

        # ---- phase 0: ingest external SUs (quota-gate, store, enqueue) --
        with jax.named_scope("ingest"):
            i_sid = jnp.clip(ingest.sid, 0, N - 1)
            state, stats = ingest_phase(state, stats, ingest, i_sid, i_sid,
                                        tables.active[i_sid], N,
                                        tables.tenant[i_sid],
                                        tables.quota, tables.burst,
                                        quarantined=state.quarantined[i_sid])

        # ---- pop this round's events (weighted-fair across tenants) -----
        with jax.named_scope("pop_accounting"):
            state, (e_sid, e_vals, e_ts, e_its, e_pop) = _pop(
                state, tables.priority, B, tables.tenant, tables.weight,
                cfg.scheduler)
            stats["popped"] += e_pop.sum(dtype=jnp.int32)
            # events whose stream was revoked/quarantined while queued
            # drop here
            e_row = jnp.clip(e_sid, 0, N - 1)
            e_real = tables.active[e_row]
            e_act = e_real & ~state.quarantined[e_row]
            e_valid = e_pop & e_act
            e_poison = e_pop & e_real & state.quarantined[e_row]
            stats["dropped_revoked"] += (e_pop & ~e_real).sum(dtype=jnp.int32)
            state = dlq_append(state, e_sid, e_vals, e_ts,
                               tables.tenant[e_row],
                               DLQ_REVOKED, e_pop & ~e_real, its=e_its)
            stats["dropped_poisoned"] += e_poison.sum(dtype=jnp.int32)
            state = dlq_append(state, e_sid, e_vals, e_ts,
                               tables.tenant[e_row],
                               DLQ_POISONED, e_poison, its=e_its)

        # ---- stage 1: subscriber dispatching ----------------------------
        # The engine applies the stale check in process_work_items'
        # keep_mask, so it asks the fanout for targets only — the Pallas
        # stream_dispatch path then skips its timestamp gather.
        with jax.named_scope("fanout"):
            targets, _ = fanout_fn(e_sid, e_ts, e_valid,
                                   tables.out_table, state.timestamps,
                                   with_early=False)
            wi_t = targets.reshape(W)
            wi_valid = (wi_t >= 0) & jnp.repeat(e_valid, F)
            wi_src = jnp.repeat(e_sid, F)
            wi_vals = jnp.repeat(e_vals, F, axis=0)
            wi_ts = jnp.repeat(e_ts, F)
            wi_its = jnp.repeat(e_its, F)
            t = jnp.clip(wi_t, 0, N - 1)

        # ---- stages 2 + 3: fetch, transform, filter ----------------------
        # the effective active mask (real & ~quarantined) gates the live
        # verdict, so a quarantined *target* cannot run or win either —
        # exactly the mask the fused kernel saw
        with jax.named_scope("apply"):
            new_vals, ts_out, live, keep, counts, badf = process_work_items(
                cfg,
                tables._replace(active=tables.active & ~state.quarantined),
                t, t, wi_src, wi_vals, wi_ts, wi_valid,
                state.values, state.timestamps)
            for k, v in counts.items():
                stats[k] = stats[k] + v

        # ---- stage 4: store, trigger actions and emit ---------------------
        with jax.named_scope("store_emit"):
            state, stats, sink = store_and_emit(cfg, tables, state, stats,
                                                t, t, wi_src, new_vals,
                                                ts_out, keep, N,
                                                wi_its=wi_its)

        # ---- fault plane: breaker window + device auto-quarantine --------
        with jax.named_scope("fault"):
            fan = (wi_t.reshape(B, F) >= 0).sum(axis=1, dtype=jnp.int32)
            fault_evt = fault_events(tables.breaker, badf, wi_valid, t,
                                     fan, e_valid, e_row, N)
            state, stats = fault_phase(
                state, stats, tables.breaker, fault_evt, tables.active,
                tables.tenant, jnp.clip(state.q_sid, 0, N - 1))
            state = state._replace(
                stats=stats,
                tenant_queued=tenant_occupancy(state, tables.tenant,
                                               cfg.n_tenants))
        return state, sink

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(1,) if donate else ())


# --------------------------------------------------------------------------
# the superstep execution plane: K rounds fused into one compiled scan
# --------------------------------------------------------------------------

class IngestRing(NamedTuple):
    """Device-resident pool of pending SUs feeding a K-round superstep.

    ``post()`` still appends host-side; at each superstep *boundary* the
    host stages the ring with one jitted edit (:func:`stage_ring`): new SU
    payloads are scattered into free slots and every slot's routing tag is
    rewritten in a single transfer.  Slots tagged ``rnd < K`` form the
    superstep's ``(K, B)`` pre-staged ingest grid — round ``rnd`` consumes
    them at grid column ``pos``; slots tagged ``rnd >= K`` are the
    persistent overflow queue: SUs (same-stream bursts longer than K
    rounds) whose payloads stay resident on device and are merely
    re-tagged at the next boundary."""
    sid: jnp.ndarray      # (R,)
    vals: jnp.ndarray     # (R, C)
    ts: jnp.ndarray       # (R,)
    its: jnp.ndarray      # (R,) ingest stamps (latency plane)
    rnd: jnp.ndarray      # (R,) target round this superstep; >= K = carried
    pos: jnp.ndarray      # (R,) column within the (K, B) grid row
    valid: jnp.ndarray    # (R,) bool — slot holds a pending SU


class SinkSpool(NamedTuple):
    """On-device emission spool of one superstep: every round's external
    sink entries appended compactly behind a fill cursor, read back once
    per superstep instead of once per round.  ``rnd`` records the round
    that produced each entry, so per-round :class:`SinkBatch` views can be
    reconstructed bit-identically (``StreamEngine.spool_sinks``).
    Emissions beyond capacity are counted in ``stats["dropped_spool"]`` —
    never silently truncated."""
    sid: jnp.ndarray      # (P,)
    vals: jnp.ndarray     # (P, C)
    ts: jnp.ndarray       # (P,)
    its: jnp.ndarray      # (P,) ingest stamps (latency plane)
    rnd: jnp.ndarray      # (P,) scan-local round; superstep-global round is
    #                       engine._last_base + rnd (see latency_records)
    fill: jnp.ndarray     # scalar int32 cursor


def init_ring(cfg: EngineConfig, K: int) -> IngestRing:
    """Empty K-round ingest ring: ``cfg.ring_slots(K)`` free slots, every
    tag at ``rnd == K`` (carried / unused)."""
    R, C = cfg.ring_slots(K), cfg.channels
    return IngestRing(
        sid=jnp.zeros((R,), jnp.int32),
        vals=jnp.zeros((R, C), jnp.float32),
        ts=jnp.zeros((R,), jnp.int32),
        its=jnp.zeros((R,), jnp.int32),
        rnd=jnp.full((R,), K, jnp.int32),
        pos=jnp.zeros((R,), jnp.int32),
        valid=jnp.zeros((R,), bool),
    )


def _init_spool(P: int, C: int) -> SinkSpool:
    return SinkSpool(
        sid=jnp.zeros((P,), jnp.int32),
        vals=jnp.zeros((P, C), jnp.float32),
        ts=jnp.zeros((P,), jnp.int32),
        its=jnp.zeros((P,), jnp.int32),
        rnd=jnp.zeros((P,), jnp.int32),
        fill=jnp.zeros((), jnp.int32),
    )


def _stage_ring(ring: IngestRing, w_slot, w_sid, w_vals, w_ts, w_its,
                rnd, pos, valid) -> IngestRing:
    """Unjitted :func:`stage_ring` body — the sharded engine vmaps it
    over the shard axis (one staging edit for every shard's ring slice
    in a single dispatch)."""
    return IngestRing(
        sid=ring.sid.at[w_slot].set(w_sid, mode="drop"),
        vals=ring.vals.at[w_slot].set(w_vals, mode="drop"),
        ts=ring.ts.at[w_slot].set(w_ts, mode="drop"),
        its=ring.its.at[w_slot].set(w_its, mode="drop"),
        rnd=jnp.asarray(rnd), pos=jnp.asarray(pos),
        valid=jnp.asarray(valid),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def stage_ring(ring: IngestRing, w_slot, w_sid, w_vals, w_ts, w_its,
               rnd, pos, valid) -> IngestRing:
    """The one host->device edit per superstep boundary: scatter newly
    posted SU payloads into free ring slots (``w_*`` are (R,)-padded;
    ``w_slot == R`` entries drop) and rewrite every slot's routing tag.
    Carried-over slots keep their payloads — only tags travel again."""
    return _stage_ring(ring, w_slot, w_sid, w_vals, w_ts, w_its,
                       rnd, pos, valid)


def ring_grid(ring: IngestRing, K: int, B: int, C: int) -> IngestBatch:
    """Materialize the (K, B) pre-staged ingest grid from the ring — each
    staged SU lands at (rnd, pos), exactly where K sequential
    ``_take_ingest`` batches would have put it."""
    use = ring.valid & (ring.rnd < K)
    cell = jnp.where(use, ring.rnd * B + ring.pos, K * B)
    return IngestBatch(
        sid=jnp.zeros((K * B,), jnp.int32)
            .at[cell].set(ring.sid, mode="drop").reshape(K, B),
        vals=jnp.zeros((K * B, C), jnp.float32)
            .at[cell].set(ring.vals, mode="drop").reshape(K, B, C),
        ts=jnp.zeros((K * B,), jnp.int32)
            .at[cell].set(ring.ts, mode="drop").reshape(K, B),
        valid=jnp.zeros((K * B,), bool)
            .at[cell].set(use, mode="drop").reshape(K, B),
        its=jnp.zeros((K * B,), jnp.int32)
            .at[cell].set(ring.its, mode="drop").reshape(K, B),
    )


def spool_append(spool: SinkSpool, sink: SinkBatch, k
                 ) -> Tuple[SinkSpool, jnp.ndarray]:
    """Append one round's valid sink entries behind the fill cursor;
    returns the spool and the per-entry overflow mask (its sum feeds
    ``dropped_spool``; the mask itself feeds the dead-letter spool)."""
    P = spool.sid.shape[0]
    add = sink.valid
    rank = spool.fill + jnp.cumsum(add.astype(jnp.int32)) - 1
    dest = jnp.where(add & (rank < P), rank, P)
    over = add & (rank >= P)
    return SinkSpool(
        sid=spool.sid.at[dest].set(sink.sid, mode="drop"),
        vals=spool.vals.at[dest].set(sink.vals, mode="drop"),
        ts=spool.ts.at[dest].set(sink.ts, mode="drop"),
        its=spool.its.at[dest].set(sink.its, mode="drop"),
        rnd=spool.rnd.at[dest].set(k, mode="drop"),
        fill=jnp.minimum(spool.fill + add.sum(dtype=jnp.int32), P),
    ), over


def scan_rounds(round_fn: Callable, state: EngineState, ring: IngestRing,
                K: int, B: int, C: int, P: int,
                tenant_by_sid: Optional[jnp.ndarray] = None,
                ) -> Tuple[EngineState, SinkSpool, IngestRing]:
    """The superstep harness shared by the single-device and sharded
    planes: materialize the (K, B) grid from the ring, ``lax.scan`` the
    round body over it spooling each round's sink, and invalidate the
    consumed ring slots.  ``round_fn(state, ingest) -> (state, sink)``.
    ``tenant_by_sid`` (indexed by sink sids) attributes spool-overflow
    dead letters to their emitting tenant."""
    with jax.named_scope("ring_grid"):
        grid = ring_grid(ring, K, B, C)

    def body(carry, xs):
        st, sp = carry
        k, ingest = xs
        st, sink = round_fn(st, ingest)
        with jax.named_scope("spool_append"):
            sp, over = spool_append(sp, sink, k)
            stats = dict(st.stats)
            stats["dropped_spool"] = stats["dropped_spool"] + \
                over.sum(dtype=jnp.int32)
            st = st._replace(stats=stats)
            s_ten = None if tenant_by_sid is None else tenant_by_sid[
                jnp.clip(sink.sid, 0, tenant_by_sid.shape[0] - 1)]
            st = dlq_append(st, sink.sid, sink.vals, sink.ts, s_ten,
                            DLQ_SPOOL, over, its=sink.its)
        return (st, sp), None

    # the loop's own work (slicing the grid, carrying the state) falls
    # under `round_loop`; the round's stages under their own scopes
    with jax.named_scope("round_loop"):
        (state, spool), _ = jax.lax.scan(
            body, (state, _init_spool(P, C)),
            (jnp.arange(K, dtype=jnp.int32), grid))
        return state, spool, ring._replace(
            valid=ring.valid & (ring.rnd >= K))


def make_superstep(
    cfg: EngineConfig,
    K: int,
    fanout_fn: Callable = fanout_reference,
    donate: bool = True,
    jit: bool = True,
    fused: Optional[bool] = None,
) -> Callable:
    """Fuse K engine rounds into one compiled ``lax.scan``.  Signature:
    ``superstep(tables, state, ring) -> (state, spool, ring)``.

    The scan body is the exact four-stage round of :func:`make_step`, so a
    K-superstep is bit-identical to K sequential ``round()`` calls; what
    changes is the host boundary: one staged ingest transfer in, one spool
    readback out, and zero device->host->device round-trips in between.
    Like the round itself, the program is static — tables are arguments,
    so admission edits applied *between* supersteps never retrace it."""
    assert K >= 1
    step = make_step(cfg, fanout_fn, jit=False, fused=fused)
    B, C = cfg.batch, cfg.channels
    P = cfg.spool_slots(K)

    def superstep(tables: DeviceTables, state: EngineState, ring: IngestRing
                  ) -> Tuple[EngineState, SinkSpool, IngestRing]:
        return scan_rounds(lambda st, ing: step(tables, st, ing),
                           state, ring, K, B, C, P, tables.tenant)

    if not jit:
        return superstep
    return jax.jit(superstep, donate_argnums=(1, 2) if donate else ())


# --------------------------------------------------------------------------
# host-side wrapper
# --------------------------------------------------------------------------

class StreamEngine:
    """Convenience wrapper owning tables, state and the compiled step."""

    def __init__(self, registry: Registry, *, fanout_fn: Callable = fanout_reference,
                 priority: Optional[np.ndarray] = None):
        if registry.cfg.n_shards > 1:
            raise ValueError(
                "cfg.n_shards > 1: build the engine with "
                "repro.core.create_engine (or ShardedStreamEngine directly)")
        self.cfg = registry.cfg
        self.registry = registry
        self.tables = DeviceTables.from_host(registry.build_tables(priority))
        self.state = init_state(self.cfg)
        self._fanout_fn = fanout_fn
        # round-fusion fallback plane: per-row fusability bitmap mirrored
        # host-side (updated at every program edit) — the fused path runs
        # only while *every* admitted program is fusable
        self._refresh_fusable()
        # compiled-closure cache (layout key -> per-path step + per-K
        # supersteps); it survives resize morphs, so revisiting a shard
        # count re-uses the already-jitted programs instead of recompiling
        self._fn_cache: Dict = {}
        self._compiled_for(
            "single", lambda fused: make_step(self.cfg, fanout_fn,
                                              fused=fused))
        self._pending: List[List] = []  # [sid, vals, ts, ring_slot|None, its]
        self.admission_rejected = 0     # host-side churn rejection counter
        # latency plane: the engine's global round counter (rounds ever run)
        # stamps each post()ed SU; _last_base is its value just before the
        # most recent round()/superstep() — spool-local round tags offset
        # from it to recover the superstep-global emission round
        self._rounds_done = 0
        self._last_base = 0
        self._ring: Optional[IngestRing] = None
        self._ring_K = 0
        self._ring_free: List[int] = []
        # durability plane: snapshot cadence (see checkpoint_to)
        self._ckpt = None
        self._steps_done = 0

    # -------------------------------------------------------------- ingest
    def post(self, stream, values: Sequence[float], ts: int,
             its: Optional[int] = None) -> None:
        """API ingress: a Web Object posts a Sensor Update (paper §III).

        ``its`` is the SU's ingest stamp for the latency plane — by default
        the engine's global round counter at post time, so ingest->sink
        latency is measured in engine rounds.  Re-submission paths
        (dead-letter redelivery, the serving bridge's response post) pass
        the *original* stamp so the latency clock keeps running across the
        detour."""
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        v = np.zeros((self.cfg.channels,), np.float32)
        v[: len(values)] = values
        if its is None:
            its = self._rounds_done
        # 4th field: the SU's ingest-ring slot once its payload is shipped
        self._pending.append([sid, v, int(ts), None, int(its)])

    @staticmethod
    def _select_wave(pending: List[List], B: int) -> Tuple[List, List]:
        """One round's ingest selection: at most one pending SU *per
        stream* (preserving order), at most B total.  Shared by the
        per-round ``_take_ingest`` and the superstep staging so both paths
        pack SUs into identical rounds."""
        take, rest, seen = [], [], set()
        for item in pending:
            if len(take) < B and item[0] not in seen:
                take.append(item)
                seen.add(item[0])
            else:
                rest.append(item)
        return take, rest

    def _take_ingest(self) -> IngestBatch:
        """At most one pending SU *per stream* per round (preserving order),
        so successive updates of one device are processed per-SU like the
        paper's runtime; same-stream bursts forced into one batch would be
        coalesced to the newest (counted in ``coalesced``).

        The batch is returned as host numpy arrays: the jitted step's
        dispatch ships them in one C++-side transfer, which is several
        times cheaper per round than four eager ``device_put`` calls
        (the per-round ingress overhead is visible at benchmark rates)."""
        B, C = self.cfg.batch, self.cfg.channels
        sid = np.zeros((B,), np.int32)
        vals = np.zeros((B, C), np.float32)
        ts = np.zeros((B,), np.int32)
        valid = np.zeros((B,), bool)
        its = np.zeros((B,), np.int32)
        take, self._pending = self._select_wave(self._pending, B)
        for i, (s, v, t, slot, stamp) in enumerate(take):
            sid[i], vals[i], ts[i], valid[i], its[i] = s, v, t, True, stamp
            if slot is not None:        # consumed via the per-round API:
                self._release_ring_slot(slot)  # release its staged ring slot
        return IngestBatch(sid, vals, ts, valid, its)

    def _release_ring_slot(self, slot) -> None:
        """Return a consumed SU's staged ingest-ring slot to the free
        pool (the sharded engine keys its pool per shard)."""
        self._ring_free.append(slot)

    # --------------------------------------------------------------- rounds
    def round(self) -> SinkBatch:
        """Run one four-stage engine round: ship the pending ingest batch,
        dispatch the compiled step, return the round's external sink."""
        self._last_base = self._rounds_done
        self.state, sink = self._step(self.tables, self.state, self._take_ingest())
        self._rounds_done += 1
        self._maybe_checkpoint()
        return sink

    def drain(self, max_rounds: int = 256) -> List[SinkBatch]:
        """Run rounds until the queue (and host backlog) is empty.  With
        ``cfg.superstep > 1`` the rounds ride the superstep plane — K
        rounds per compiled call, one sink readback per superstep — and
        the returned per-round sink batches are reconstructed from the
        spool (bit-identical to the per-round path)."""
        K = self.cfg.superstep
        if K <= 1:
            sinks = []
            for _ in range(max_rounds):
                busy_host = bool(self._pending)
                sinks.append(self.round())
                if not busy_host and not bool(self.state.q_valid.any()):
                    break
            return sinks
        sinks = []
        for spool in self.drain_spools(K, max_rounds):
            sinks.extend(self.spool_sinks(spool))
        return sinks

    def drain_spools(self, K: Optional[int] = None, max_rounds: int = 256):
        """Yield one :class:`SinkSpool` per superstep until the host
        backlog and device queue are empty.  Rounds are quantized to K;
        never exceeds ``max_rounds`` (a latency bound to callers) except
        when ``max_rounds < K``, which still runs one whole superstep.
        The one drain-until-empty protocol for every spool consumer
        (``drain()``, the serving bridge's ``serve``)."""
        K = K or self.cfg.superstep
        for _ in range(max(max_rounds // K, 1)):
            busy_host = bool(self._pending)
            yield self.superstep(K)
            if not busy_host and not bool(self.state.q_valid.any()):
                break

    # ----------------------------------------------------------- supersteps
    def _assign_rounds(self, K: int) -> List[Tuple[List, int, int]]:
        """Pack pending SUs into the (K, B) ingest grid by simulating K
        sequential ``_take_ingest`` selections; returns ``(entry, round,
        column)`` triples and leaves the unconsumed tail in ``_pending``."""
        B = self.cfg.batch
        assigned, pend = [], self._pending
        for k in range(K):
            take, pend = self._select_wave(pend, B)
            assigned += [(e, k, i) for i, e in enumerate(take)]
        self._pending = pend
        return assigned

    def _compiled_for(self, key, build: Callable) -> None:
        """Install the step/superstep programs for a layout, re-using this
        engine's closure cache when the layout was visited before — a
        resize back to a previously seen shard count then costs zero
        recompilation.  ``key`` identifies everything the closures are
        specialized on (shard count, per-shard row count, mesh devices);
        ``build(fused)`` makes the round-step closure on a miss.  Each
        layout caches both round paths ("fused"/"staged") independently
        and lazily — :meth:`_select_path` flips between them without
        recompiling.  The per-K superstep dict is cached by reference, so
        lazily-built K variants are kept across revisits too."""
        cache = self.__dict__.setdefault("_fn_cache", {})
        hit = cache.get(key)
        if hit is None:
            hit = cache[key] = {}
        self._fn_layout = (hit, build)
        self._select_path()

    def _round_path(self) -> str:
        """The round implementation the next dispatch takes: "fused" while
        the config asks for fusion and every admitted program is fusable
        (no transcendental opcodes — ``round_fuse.ref.FUSABLE_OPS``),
        "staged" otherwise.  Re-evaluated at every program edit; both
        paths are bit-identical, so the flip is invisible to results."""
        return "fused" if (self.cfg.fused_round
                           and self.cfg.scheduler == "packed"
                           and bool(self._fusable_rows.all())) else "staged"

    def _select_path(self) -> None:
        """(Re)install the compiled step/supersteps of the current round
        path for the current layout — a dict lookup when the path was
        built before, one jit trace when not."""
        layout, build = self._fn_layout
        self._path = path = self._round_path()
        hit = layout.get(path)
        if hit is None:
            hit = layout[path] = (build(path == "fused"), {})
        self._step, self._superstep_fns = hit

    def _refresh_fusable(self) -> None:
        """Recompute the per-row fusability bitmap from the device program
        table (full-table edits: construction, rewire, restore, resize)
        and re-select the round path."""
        from repro.kernels.round_fuse.ref import fusable_rows
        self._fusable_rows = fusable_rows(np.asarray(self.tables.progs))
        if "_fn_layout" in self.__dict__:
            self._select_path()

    def _note_program(self, row: Tuple, prog: Optional[np.ndarray]) -> None:
        """Single-row fusability update (admit/revoke/swap program edits);
        ``prog=None`` marks the row trivially fusable (empty program)."""
        from repro.kernels.round_fuse.ref import fusable_program
        self._fusable_rows[row] = fusable_program(prog)
        self._select_path()

    def _superstep_fn(self, K: int) -> Callable:
        fn = self._superstep_fns.get(K)
        if fn is None:
            fn = self._superstep_fns[K] = make_superstep(
                self.cfg, K, self._fanout_fn, fused=self._path == "fused")
        return fn

    def _stage(self, K: int) -> Tuple[int, int, int]:
        """Superstep boundary: assign rounds, ship new payloads into free
        ring slots, rewrite every slot's routing tag — one jitted edit.
        SUs already resident (the overflow queue) are only re-tagged.
        Returns the counts the ``repro.stage`` span carries: SUs assigned
        to the (K, B) grid, payloads shipped, SUs left pending."""
        R, C = self.cfg.ring_slots(K), self.cfg.channels
        if self._ring is None or self._ring_K != K:
            self._ring, self._ring_K = init_ring(self.cfg, K), K
            self._ring_free = list(range(R))
            for e in self._pending:     # slots of the old ring are void
                e[3] = None
        assigned = self._assign_rounds(K)
        # every SU consumed this superstep needs its payload on device;
        # spill slots of carried SUs if free ones run out (host re-ships
        # the victim later — it keeps every payload until consumption)
        slotted = [e for e in self._pending if e[3] is not None]
        writes = []
        for e, _k, _i in assigned:
            if e[3] is None:
                if self._ring_free:
                    e[3] = self._ring_free.pop()
                else:                   # youngest carried SU spills its slot
                    victim = slotted.pop()
                    e[3], victim[3] = victim[3], None
                writes.append(e)
        # pre-ship overflow: earliest carried SUs claim leftover slots
        for e in self._pending:
            if not self._ring_free:
                break
            if e[3] is None:
                e[3] = self._ring_free.pop()
                writes.append(e)
        w_slot = np.full((R,), R, np.int32)
        w_sid = np.zeros((R,), np.int32)
        w_vals = np.zeros((R, C), np.float32)
        w_ts = np.zeros((R,), np.int32)
        w_its = np.zeros((R,), np.int32)
        for j, e in enumerate(writes):
            w_slot[j], w_sid[j], w_vals[j], w_ts[j], w_its[j] = \
                e[3], e[0], e[1], e[2], e[4]
        rnd = np.full((R,), K, np.int32)
        pos = np.zeros((R,), np.int32)
        valid = np.zeros((R,), bool)
        for e, k, i in assigned:
            rnd[e[3]], pos[e[3]], valid[e[3]] = k, i, True
        for e in self._pending:
            if e[3] is not None:
                valid[e[3]] = True      # carried overflow stays resident
        self._ring = stage_ring(self._ring, w_slot, w_sid, w_vals, w_ts,
                                w_its, rnd, pos, valid)
        self._ring_free += [e[3] for e, _k, _i in assigned]
        return len(assigned), len(writes), len(self._pending)

    def superstep(self, K: Optional[int] = None) -> SinkSpool:
        """Run K fused rounds: stage the ingest ring, execute the compiled
        scan, return the sink spool (read it back with ``spool_sinks`` or
        feed it to the serving bridge's ``pump_spool``).

        Host spans (``jax.profiler.TraceAnnotation``, inert without an
        active profiler): ``repro.stage`` around the staging, carrying its
        ``sus``/``shipped``/``carried`` counts, and ``repro.dispatch``
        around the superstep program call."""
        K = K or self.cfg.superstep
        with jax.profiler.TraceAnnotation("repro.stage") as span:
            sus, shipped, carried = self._stage(K)
            span.set_metadata(sus=sus, shipped=shipped, carried=carried)
        self._last_base = self._rounds_done
        with jax.profiler.TraceAnnotation("repro.dispatch"):
            spool = self._run_superstep(K)
        self._rounds_done += K
        self._maybe_checkpoint()
        return spool

    def _run_superstep(self, K: int) -> SinkSpool:
        """Hook: the sharded engine threads its gmap through here."""
        self.state, spool, self._ring = self._superstep_fn(K)(
            self.tables, self.state, self._ring)
        return spool

    def spool_sinks(self, spool: SinkSpool,
                    K: Optional[int] = None) -> List[SinkBatch]:
        """Reconstruct one superstep's per-round :class:`SinkBatch` list
        from the spool — bit-identical to K sequential ``round()`` sinks
        (provided the spool did not overflow).  Host spans:
        ``repro.spool.read`` (the device-to-host copies, carrying the
        spooled ``records``) and ``repro.spool.decode`` (the rebuild)."""
        S, C = self.cfg.sink_buffer, self.cfg.channels
        with jax.profiler.TraceAnnotation("repro.spool.read") as span:
            sid = np.asarray(spool.sid)
            vals = np.asarray(spool.vals)
            ts = np.asarray(spool.ts)
            its = np.asarray(spool.its)
            rnd = np.asarray(spool.rnd)
            fill = int(spool.fill)
            span.set_metadata(records=fill)
        K = K or self._ring_K or (int(rnd[:fill].max()) + 1 if fill else 1)
        sinks = []
        with jax.profiler.TraceAnnotation("repro.spool.decode"):
            for k in range(K):
                b_sid = np.zeros((S,), np.int32)
                b_vals = np.zeros((S, C), np.float32)
                b_ts = np.zeros((S,), np.int32)
                b_valid = np.zeros((S,), bool)
                b_its = np.zeros((S,), np.int32)
                idx = np.nonzero(rnd[:fill] == k)[0]
                n = len(idx)
                b_sid[:n], b_vals[:n], b_ts[:n] = sid[idx], vals[idx], ts[idx]
                b_its[:n] = its[idx]
                b_valid[:n] = True
                # host arrays: the spool was already read back, consumers
                # read these with np.asarray — no device round-trip
                sinks.append(SinkBatch(b_sid, b_vals, b_ts, b_valid, b_its))
        return sinks

    def latency_records(self, source, base: Optional[int] = None
                        ) -> Dict[str, np.ndarray]:
        """Per-record ingest->sink latency readback — the latency plane's
        host endpoint.  ``source`` is a :class:`SinkSpool` (one superstep), a
        :class:`SinkBatch` (one round), or a list of either; ``base`` is
        the engine-global round index of the source's *first* round
        (default: ``_last_base``, i.e. the most recent
        ``round()``/``superstep()`` call).  Returns flat host arrays
        ``{"sid", "tenant", "its", "round", "latency"}`` over the valid
        records: ``round`` is the superstep-global emission round
        (``base + scan-local spool round`` — NOT the scan-local tag, which
        restarts at 0 every superstep), ``latency = round - its`` in engine
        rounds, and ``tenant`` resolves through the registry (``-1`` for
        unregistered sids).  Pure readback of arrays the sink already
        carries: zero extra device traffic, zero retraces."""
        if base is None:
            base = self._last_base
        sources = source if isinstance(source, list) else [source]
        batches: List[Tuple[SinkBatch, int]] = []   # (batch, emission round)
        for src in sources:
            if hasattr(src, "fill"):                # a SinkSpool
                for k, b in enumerate(self.spool_sinks(src)):
                    batches.append((b, base + k))
                base += self._ring_K or 1
            else:                                   # a SinkBatch
                batches.append((src, base))
                base += 1
        t_of = np.full((self.cfg.n_streams,), -1, np.int32)
        for s in self.registry.streams:
            if s is not None:
                t_of[s.sid] = s.tenant
        out = {k: [] for k in ("sid", "tenant", "its", "round", "latency")}
        for b, rnd in batches:
            sid = np.asarray(b.sid).reshape(-1)
            its = np.asarray(b.its).reshape(-1)
            valid = np.asarray(b.valid).reshape(-1)
            idx = np.nonzero(valid)[0]
            s = sid[idx].astype(np.int32)
            i = its[idx].astype(np.int32)
            out["sid"].append(s)
            out["tenant"].append(t_of[np.clip(s, 0, t_of.shape[0] - 1)])
            out["its"].append(i)
            out["round"].append(np.full(idx.shape, rnd, np.int32))
            out["latency"].append(np.full(idx.shape, rnd, np.int32) - i)
        return {k: (np.concatenate(v) if v else np.zeros((0,), np.int32))
                for k, v in out.items()}

    # ------------------------------------------------- dynamic admission
    # Live topology churn: every method below mutates the running engine's
    # device tables through the jitted table-edit ops in
    # :mod:`repro.core.admission` — O(table-edit), zero recompilation.
    # Capacity rejections return None/False and count in
    # ``admission_rejected`` (the host mirror of the paper's REST errors).

    def _table_row(self, sid: int) -> Tuple:
        """Index tuple of stream ``sid``'s row in the device tables; the
        sharded engine overrides this to address ``(shard, local)``."""
        return (np.int32(sid),)

    def _place_sid(self, sid: int, tid: int, priority: int) -> None:
        """Hook: the sharded engine routes the sid to a shard here."""

    def _released_sid(self, sid: int) -> None:
        """Hook: the sharded engine frees the sid's shard slot here."""

    def _sync_admitted(self) -> None:
        """Hook: the sharded engine re-pins device shardings here so the
        compiled round sees identically-sharded inputs (no retrace)."""

    def admit_stream(self, tenant, name: str, channels: Sequence[str],
                     *, priority: int = 0, service_object=None):
        """Admit a new simple (device-fed) stream on the *running* engine.
        Returns the Stream, or ``None`` when capacity is exhausted (the
        rejection is counted)."""
        try:
            s = self.registry.create_stream(tenant, name, channels,
                                            service_object=service_object)
        except CapacityError:
            self.admission_rejected += 1
            return None
        self._place_sid(s.sid, tenant.tid, priority)
        self._admit_row(s, priority)
        return s

    def admit_composite(self, tenant, name: str, channels: Sequence[str],
                        inputs: Sequence, transform: Optional[Dict[str, str]]
                        = None, *, pre_filter: Optional[str] = None,
                        post_filter: Optional[str] = None, priority: int = 0,
                        service_object=None, model_backed: bool = False):
        """Admit a composite stream (Service Object + subscriptions) live.
        Returns the Stream, or ``None`` on any capacity rejection."""
        try:
            s = self.registry.create_composite(
                tenant, name, channels, inputs, transform or {},
                pre_filter=pre_filter, post_filter=post_filter,
                service_object=service_object, model_backed=model_backed)
        except CapacityError:
            self.admission_rejected += 1
            return None
        self._place_sid(s.sid, tenant.tid, priority)
        self._admit_row(s, priority)
        return s

    def _admit_row(self, s, priority: int) -> None:
        from repro.core import admission
        try:
            if s.composite:
                prog, consts = self.registry._compile_stream(s)
            else:
                prog, consts = pvm.empty_program(self.cfg.prog_len,
                                                 self.cfg.n_consts)
        except Exception:
            # bad user code must not leave a half-admitted stream behind
            self.registry.remove_stream(s.sid)
            self._released_sid(s.sid)
            raise
        self.tables, self.state = admission.admit_stream(
            self.tables, self.state, self._table_row(s.sid),
            np.int32(s.tenant), np.int32(len(s.channels)),
            np.bool_(s.composite), np.bool_(s.model_backed),
            np.int32(priority), prog, consts)
        for src_sid in s.inputs:      # same append order as build_tables
            self._admit_edge(s.sid, src_sid)
        self._note_program(self._table_row(s.sid), prog)
        self._sync_admitted()

    def revoke_stream(self, stream) -> None:
        """Revoke a stream live: its row is cleared, every subscription
        referencing it is severed, queued SUs are purged into the
        ``dropped_revoked`` counter, and the sid is recycled by the next
        admission."""
        from repro.core import admission
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        self.registry.remove_stream(sid)
        self.tables, self.state = admission.revoke_stream(
            self.tables, self.state, self._table_row(sid), np.int32(sid))
        self._released_sid(sid)
        self._note_program(self._table_row(sid), None)  # row is NOPs now
        self._sync_admitted()

    def admit_subscription(self, stream, new_input, *,
                           replay: bool = False) -> bool:
        """Add a subscription edge to a running composite.  Returns False
        (counted) when in/out-degree capacity is exhausted.  With
        ``replay=True`` (and ``cfg.retention_slots > 0``), ``new_input``'s
        retained emissions are re-enqueued oldest-first *before* live data,
        so the late joiner catches up on history — at-least-once: existing
        subscribers see the replayed SUs too but discard them as stale
        (Listing-2 ``keep_mask``), while the joiner (never-emitted, ts at
        ``INT_MIN``) processes all of them.  Replay is a jitted requeue
        table edit — zero retraces under churn."""
        try:
            self.registry.subscribe(stream, new_input)
        except CapacityError:
            self.admission_rejected += 1
            return False
        self._admit_edge(stream.sid, new_input.sid)
        self._sync_admitted()
        if replay:
            self._replay_retained(new_input)
        return True

    def revoke_subscription(self, stream, old_input) -> None:
        """Remove one subscription edge from a running composite."""
        from repro.core import admission
        self.registry.unsubscribe(stream, old_input)
        self.tables, _ = admission.revoke_subscription(
            self.tables, self._table_row(stream.sid),
            self._table_row(old_input.sid),
            np.int32(stream.sid), np.int32(old_input.sid))
        self._sync_admitted()

    def _admit_edge(self, target_sid: int, src_sid: int) -> None:
        from repro.core import admission
        self.tables, ok = admission.admit_subscription(
            self.tables, self._table_row(target_sid),
            self._table_row(src_sid),
            np.int32(target_sid), np.int32(src_sid))
        if not bool(ok):
            # the registry pre-checked capacity and liveness, so a device
            # rejection means the host mirror and tables diverged
            raise RuntimeError(
                f"device tables rejected edge {src_sid}->{target_sid} the "
                "registry accepted (host/device mismatch)")

    def swap_program(self, stream, transform: Dict[str, str],
                     pre_filter: Optional[str] = None,
                     post_filter: Optional[str] = None) -> None:
        """Replace a composite stream's user code *live* — the tables are
        data, the compiled step is untouched (paper §IV-F)."""
        from repro.core import admission
        s = self.registry.stream_of(
            stream.sid if hasattr(stream, "sid") else int(stream))
        if not s.composite:
            raise ValueError("only composite streams carry user code")
        s.transform = dict(transform)
        s.pre_filter = pre_filter
        s.post_filter = post_filter
        prog, consts = self.registry._compile_stream(s)
        self.tables = admission.swap_program(
            self.tables, self._table_row(s.sid), prog, consts)
        self._note_program(self._table_row(s.sid), prog)
        self._sync_admitted()

    def inject_code(self, stream, transform: Dict[str, str],
                    pre_filter: Optional[str] = None,
                    post_filter: Optional[str] = None) -> None:
        """Back-compat alias of :meth:`swap_program` (its pre-admission-
        plane name)."""
        self.swap_program(stream, transform, pre_filter, post_filter)

    def rewire(self) -> None:
        """Re-lower the registry after subscribe()/new streams — still no
        recompilation (same-shaped tables).  The per-tenant QoS tables
        (weight/quota/burst) and the breaker knobs are preserved: they are
        placement-independent data the registry does not mirror."""
        prio = np.asarray(self.tables.priority)
        self.tables = DeviceTables.from_host(
            self.registry.build_tables(prio))._replace(
                weight=self.tables.weight, quota=self.tables.quota,
                burst=self.tables.burst, breaker=self.tables.breaker)
        self._refresh_fusable()

    # ----------------------------------------------------- tenant QoS plane
    @staticmethod
    def _tid(tenant) -> np.int32:
        return np.int32(tenant.tid if hasattr(tenant, "tid") else int(tenant))

    def set_weight(self, tenant, weight: int) -> None:
        """Set a tenant's fair-share weight *live* — one jitted table edit
        (:func:`repro.core.admission.set_weight`), zero retraces.  Queued
        SUs of backlogged tenants are then popped proportionally to their
        weights (see :func:`_pop`); ``weight=0`` (the default) exempts the
        tenant from shaping.  Weights are clipped to ``[0, FAIR_SCALE]``."""
        from repro.core import admission
        self.tables = admission.set_weight(self.tables, self._tid(tenant),
                                           np.int32(weight))
        self._sync_admitted()

    def set_quota(self, tenant, quota: int,
                  burst: Optional[int] = None) -> None:
        """Set a tenant's ingest quota *live*: a token bucket refilled by
        ``quota`` tokens per engine round up to ``burst`` (default
        ``quota``).  Arrivals beyond the bucket are shed into
        ``dropped_quota`` instead of crowding the queue; ``quota=0`` (the
        default) removes the cap.  One jitted table edit, zero retraces."""
        from repro.core import admission
        b = quota if burst is None else burst
        self.tables, self.state = admission.set_quota(
            self.tables, self.state, self._tid(tenant),
            np.int32(quota), np.int32(b))
        self._sync_admitted()

    # ------------------------------------------------- fault-isolation plane
    def set_breaker(self, window: Optional[int] = None,
                    threshold: Optional[int] = None,
                    amp_ceiling: Optional[int] = None) -> None:
        """Tune the circuit breaker *live* — one jitted table edit, zero
        retraces (the knobs are runtime data like the QoS tables).  A
        stream accumulating ``threshold`` faults (non-finite program
        output, or dispatch fan-out over ``amp_ceiling``) within a
        ``window``-round span is auto-quarantined on device.
        ``threshold=0`` disarms tripping (faults still count);
        ``amp_ceiling=0`` disarms amplification detection.  Omitted knobs
        keep their current values."""
        from repro.core import admission
        cur = np.asarray(self.tables.breaker).reshape(-1, 3)[0]
        w = cur[0] if window is None else int(window)
        f = cur[1] if threshold is None else int(threshold)
        c = cur[2] if amp_ceiling is None else int(amp_ceiling)
        assert w >= 1 and f >= 0 and c >= 0
        self.tables = admission.set_breaker(
            self.tables, np.asarray([w, f, c], np.int32))
        self._sync_admitted()

    def quarantine(self, stream) -> None:
        """Quarantine a stream by hand (the breaker's trip action, host-
        triggered): its quarantined bit flips, queued SUs purge to the DLQ
        as ``poisoned``, and the ingest/pop gates shed everything addressed
        to it until :meth:`unquarantine`.  Unlike :meth:`revoke_stream` the
        row keeps its registration, program and subscriptions — quarantine
        is reversible without re-admission.  One jitted edit, zero
        retraces; idempotent."""
        from repro.core import admission
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        self.state = admission.quarantine_stream(
            self.tables, self.state, self._table_row(sid), np.int32(sid))
        self._sync_admitted()

    def unquarantine(self, stream) -> None:
        """Lift a stream's quarantine and reset its breaker window
        (``fault_count``/``fault_epoch`` zero; the lifetime
        ``fault_total`` survives for supervisor blame).  The stream
        resumes exactly where its table row left off; its dead-lettered
        SUs come back through :meth:`redeliver`."""
        from repro.core import admission
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        self.state = admission.unquarantine_stream(
            self.state, self._table_row(sid))
        self._sync_admitted()

    def fault_counters(self) -> Dict[str, np.ndarray]:
        """The fault plane's per-stream counters as by-sid host arrays:
        ``quarantined`` (bool), ``fault_count`` (faults in the current
        breaker window) and ``fault_total`` (lifetime faults — the
        supervisor's blame signal).  Gathered across shards on the sharded
        engine."""
        out = {}
        for key, field in (("quarantined", "quarantined"),
                           ("fault_count", "fault_count"),
                           ("fault_total", "fault_total")):
            a = np.asarray(getattr(self.state, field))
            if a.ndim == 2:             # sharded: (S, L) -> by sid
                a = a.reshape(-1)[self.plan.sid_to_flat]
            out[key] = a
        return out

    def is_quarantined(self, stream) -> bool:
        """Whether ``stream``'s row is currently quarantined."""
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        return bool(self.state.quarantined[self._table_row(sid)])

    def tenant_backlog(self, tenant=None):
        """Per-tenant pending-SU queue occupancy after the last round —
        the backpressure signal (summed across shards on the sharded
        engine).  Returns the int for one ``tenant``, or the full
        ``(n_tenants,)`` numpy array when ``tenant is None``.  The serving
        bridge throttles a tenant's pump when this crosses its
        watermark."""
        occ = np.asarray(self.state.tenant_queued)
        if occ.ndim == 2:
            occ = occ.sum(axis=0)
        if tenant is None:
            return occ
        return int(occ[self._tid(tenant)])

    def tenant_counters(self) -> Dict[str, np.ndarray]:
        """Per-tenant counters as host arrays (summed across shards):
        ``emitted`` (stage-4 emissions by owner), ``queued`` (occupancy
        after the last round), ``dropped_quota`` (SUs shed over quota) and
        ``dropped_overflow`` (queue/exchange slots lost to contention)."""
        out = {}
        for key, field in (("emitted", "tenant_emitted"),
                           ("queued", "tenant_queued"),
                           ("dropped_quota", "tenant_dropped_quota"),
                           ("dropped_overflow", "tenant_dropped_overflow")):
            a = np.asarray(getattr(self.state, field))
            out[key] = a.sum(axis=0) if a.ndim == 2 else a
        return out

    # ------------------------------------------------- durability & replay
    def snapshot(self) -> Tuple[Dict[str, np.ndarray], dict]:
        """Capture the full engine as ``(arrays, meta)``: device tables,
        engine state (stats included), and the host-side pending backlog,
        plus a JSON-able ``meta`` holding the registry mirror and host
        counters.  The ingest ring is deliberately *not* captured — every
        unconsumed SU payload is retained host-side in the pending list
        (the ring is a device cache of it), so restore re-stages from the
        backlog alone and the continuation is bit-identical.  Feed the pair
        to :func:`restore_engine` (directly, or through a checkpoint)."""
        arrays: Dict[str, np.ndarray] = {}
        for f in DeviceTables._fields:
            arrays[f"tables/{f}"] = np.asarray(getattr(self.tables, f))
        for f in EngineState._fields:
            if f != "stats":
                arrays[f"state/{f}"] = np.asarray(getattr(self.state, f))
        for k in STAT_KEYS:
            arrays[f"state/stats/{k}"] = np.asarray(self.state.stats[k])
        C = self.cfg.channels
        arrays["pending/sid"] = np.array(
            [e[0] for e in self._pending], np.int32)
        arrays["pending/vals"] = (
            np.stack([e[1] for e in self._pending]).astype(np.float32)
            if self._pending else np.zeros((0, C), np.float32))
        arrays["pending/ts"] = np.array(
            [e[2] for e in self._pending], np.int32)
        arrays["pending/its"] = np.array(
            [e[4] for e in self._pending], np.int32)
        meta = {"format": 1, "kind": "single",
                "registry": self.registry.to_snapshot(),
                "admission_rejected": self.admission_rejected,
                "steps_done": self._steps_done,
                "rounds_done": self._rounds_done}
        return arrays, meta

    def _install_snapshot(self, arrays: Dict[str, np.ndarray],
                          meta: dict) -> None:
        """Overwrite this (freshly built) engine with a snapshot's tables,
        state and backlog — the restore half of :meth:`snapshot`.
        Pre-fault-plane snapshots default the breaker table from the
        config and the fault leaves/stats to zero (nothing quarantined),
        so old checkpoints stay restorable."""
        brk = arrays.get("tables/breaker")
        if brk is None:
            brk = np.array([self.cfg.fault_window, self.cfg.fault_threshold,
                            self.cfg.fault_amp_ceiling], np.int32)
            if arrays["tables/active"].ndim == 2:
                brk = np.tile(brk[None], (arrays["tables/active"].shape[0], 1))
        self.tables = DeviceTables(**dict(
            {f: jnp.asarray(arrays[f"tables/{f}"])
             for f in DeviceTables._fields if f != "breaker"},
            breaker=jnp.asarray(brk)))
        row_shape = arrays["state/timestamps"].shape
        fault_fill = {
            "quarantined": np.zeros(row_shape, bool),
            "fault_count": np.zeros(row_shape, np.int32),
            "fault_epoch": np.zeros(row_shape, np.int32),
            "fault_total": np.zeros(row_shape, np.int32),
            "round_idx": np.zeros(np.asarray(arrays["state/seq"]).shape,
                                  np.int32),
        }
        st = {f: jnp.asarray(arrays[f"state/{f}"]
                             if f"state/{f}" in arrays else fault_fill[f])
              for f in EngineState._fields if f != "stats"}
        stat0 = np.zeros_like(np.asarray(arrays["state/stats/ingested"]))
        st["stats"] = {k: jnp.asarray(arrays.get(f"state/stats/{k}", stat0))
                       for k in STAT_KEYS}
        self.state = EngineState(**st)
        p_sid, p_vals, p_ts = (arrays["pending/sid"], arrays["pending/vals"],
                               arrays["pending/ts"])
        p_its = arrays.get("pending/its")
        if p_its is None:               # pre-latency-plane snapshot
            p_its = np.zeros_like(p_sid)
        # ring slots are process-local; restored SUs re-stage from here
        self._pending = [[int(p_sid[i]), np.array(p_vals[i], np.float32),
                          int(p_ts[i]), None, int(p_its[i])]
                         for i in range(p_sid.shape[0])]
        self.admission_rejected = int(meta.get("admission_rejected", 0))
        self._steps_done = int(meta.get("steps_done", 0))
        self._rounds_done = int(meta.get("rounds_done", 0))
        self._last_base = self._rounds_done
        self._ring, self._ring_K, self._ring_free = None, 0, []
        self._refresh_fusable()
        self._sync_admitted()

    def checkpoint_to(self, path: Optional[str], keep: int = 3):
        """Attach a :class:`~repro.checkpoint.ckpt.CheckpointManager` at
        ``path``: every ``cfg.checkpoint_every``-th superstep boundary
        (rounds count as supersteps of one) snapshots the engine and writes
        it asynchronously, keeping the newest ``keep`` checkpoints.
        Returns the manager (use its ``wait()`` before reading the
        directory; recover with :func:`restore_engine`).  ``path=None``
        detaches the manager after awaiting any in-flight write."""
        from repro.checkpoint.ckpt import CheckpointManager
        if path is None:
            if self._ckpt is not None:
                self._ckpt.wait()
            self._ckpt = None
            return None
        self._ckpt = CheckpointManager(path, keep=keep)
        return self._ckpt

    def _maybe_checkpoint(self) -> None:
        """Superstep-boundary hook: count the boundary and, when the
        cadence lands and a manager is attached, snapshot + async-save."""
        self._steps_done += 1
        every = self.cfg.checkpoint_every
        if self._ckpt is not None and every > 0 \
                and self._steps_done % every == 0:
            arrays, meta = self.snapshot()
            self._ckpt.save_async(self._steps_done, arrays, extra=meta)

    def dead_letters(self, clear: bool = True) -> List[DeadLetter]:
        """Drain the device dead-letter spool: every SU dropped into a
        ``dropped_*`` counter since the last drain (up to ``cfg.dlq_slots``
        per drain interval), as host :class:`DeadLetter` records in drop
        order (shard-major on the sharded engine).  ``clear`` resets the
        spool cursor so subsequent drops refill from the top."""
        sid = np.asarray(self.state.dlq_sid)
        if sid.shape[-1] == 0:
            return []
        vals = np.asarray(self.state.dlq_vals)
        ts = np.asarray(self.state.dlq_ts)
        its = np.asarray(self.state.dlq_its)
        reason = np.asarray(self.state.dlq_reason)
        tenant = np.asarray(self.state.dlq_tenant)
        fill = np.atleast_1d(np.asarray(self.state.dlq_fill))
        if sid.ndim == 1:
            sid, vals, ts, its = sid[None], vals[None], ts[None], its[None]
            reason, tenant = reason[None], tenant[None]
        letters = [
            DeadLetter(int(sid[s, i]), np.array(vals[s, i]), int(ts[s, i]),
                       DLQ_REASONS[int(reason[s, i])], int(tenant[s, i]),
                       int(its[s, i]))
            for s in range(sid.shape[0]) for i in range(int(fill[s]))]
        if clear and letters:
            from repro.core import admission
            self.state = admission.clear_dead_letters(self.state)
            self._sync_admitted()
        return letters

    def redeliver(self, letters: Optional[List[DeadLetter]] = None) -> int:
        """Resubmit dead letters (default: drain-and-clear the spool now).
        Quota-shed SUs were rejected *before* phase 0 stored them, so they
        re-enter through normal ingest (store + fanout + admission — a
        still-exhausted quota sheds them again); every other class was
        already stored when it dropped, so it re-enqueues through the
        jitted requeue edit, bypassing the phase-0 stale gate so
        historical timestamps survive.  Letters whose stream is no longer
        admittable — revoked *or* still quarantined — are refused: they
        stay in the spool (re-appended through the jitted respool edit)
        and are counted in ``stats["redeliver_rejected"]``, so an operator
        who redelivers before lifting a quarantine loses nothing and sees
        the refusal in the counters.  Re-enqueues that overflow the queue
        drop (and dead-letter) again.  Returns the number submitted."""
        if letters is None:
            letters = self.dead_letters(clear=True)
        qmask = self.fault_counters()["quarantined"]
        live, rejected = [], []
        for lt in letters:
            registered = (0 <= lt.sid < len(self.registry.streams)
                          and self.registry.streams[lt.sid] is not None)
            if registered and not bool(qmask[lt.sid]):
                live.append(lt)
            else:
                rejected.append(lt)
        for lt in live:
            if lt.reason == "quota":
                self.post(lt.sid, lt.vals, lt.ts, its=lt.its)
        self._requeue_batch([(lt.sid, lt.vals, lt.ts, lt.tenant, lt.its)
                             for lt in live if lt.reason != "quota"])
        self._respool_rejected(rejected)
        return len(live)

    def _respool_rejected(self, letters: List[DeadLetter]) -> None:
        """Put refused dead letters back in the spool (original reason and
        stamps preserved) and count them — one padded jitted edit per
        chunk, same static width as ``_requeue_batch`` so redelivery churn
        never retraces."""
        if not letters:
            return
        W = max(self.cfg.retention_slots, self.cfg.dlq_slots, 1)
        C = self.cfg.channels
        for ofs in range(0, len(letters), W):
            chunk = letters[ofs:ofs + W]
            sid = np.zeros((W,), np.int32)
            vals = np.zeros((W, C), np.float32)
            ts = np.zeros((W,), np.int32)
            reason = np.zeros((W,), np.int32)
            tenant = np.zeros((W,), np.int32)
            its = np.zeros((W,), np.int32)
            valid = np.zeros((W,), bool)
            for i, lt in enumerate(chunk):
                sid[i], vals[i], ts[i] = lt.sid, lt.vals, lt.ts
                reason[i] = DLQ_REASONS.index(lt.reason)
                tenant[i], its[i], valid[i] = lt.tenant, lt.its, True
            self._apply_respool(sid, vals, ts, reason, tenant, its, valid)

    def _apply_respool(self, sid, vals, ts, reason, tenant, its,
                       valid) -> None:
        """Hook: one padded respool edit (the sharded engine routes each
        letter to its owner shard here)."""
        from repro.core import admission
        self.state = admission.respool(
            self.state, jnp.asarray(sid), jnp.asarray(vals),
            jnp.asarray(ts), jnp.asarray(reason), jnp.asarray(tenant),
            jnp.asarray(its), jnp.asarray(valid))
        self._sync_admitted()

    def _replay_retained(self, src) -> int:
        """Re-enqueue ``src``'s retained emissions oldest-first — the
        replay half of ``admit_subscription(..., replay=True)``."""
        Rr = self.cfg.retention_slots
        sid = src.sid if hasattr(src, "sid") else int(src)
        if Rr == 0:
            return 0
        row = self._table_row(sid)
        count = int(self.state.ret_count[row])
        if count == 0:
            return 0
        vals = np.asarray(self.state.ret_vals[row])
        ts = np.asarray(self.state.ret_ts[row])
        r_its = np.asarray(self.state.ret_its[row])
        tenant = self.registry.stream_of(sid).tenant
        n = min(count, Rr)
        # replayed emissions keep their *original* ingest stamp — the
        # latency clock of a replayed SU spans the whole detour
        items = [(sid, vals[(count - n + i) % Rr],
                  int(ts[(count - n + i) % Rr]), tenant,
                  int(r_its[(count - n + i) % Rr])) for i in range(n)]
        return self._requeue_batch(items)

    def _requeue_batch(self, items: List[Tuple]) -> int:
        """Ship ``(sid, vals, ts, tenant, its)`` items into the queue
        through the requeue table edit, chunked to one static pad width so
        churn never retraces."""
        if not items:
            return 0
        W = max(self.cfg.retention_slots, self.cfg.dlq_slots, 1)
        C = self.cfg.channels
        for ofs in range(0, len(items), W):
            chunk = items[ofs:ofs + W]
            sid = np.zeros((W,), np.int32)
            vals = np.zeros((W, C), np.float32)
            ts = np.zeros((W,), np.int32)
            valid = np.zeros((W,), bool)
            tenant = np.zeros((W,), np.int32)
            its = np.zeros((W,), np.int32)
            for i, (s, v, t, tn, stamp) in enumerate(chunk):
                sid[i], vals[i], ts[i] = s, v, t
                valid[i], tenant[i], its[i] = True, tn, stamp
            self._apply_requeue(sid, vals, ts, valid, tenant, its)
        return len(items)

    def _apply_requeue(self, sid, vals, ts, valid, tenant, its) -> None:
        """Hook: one padded requeue edit (the sharded engine routes each
        item to its owner shard here)."""
        from repro.core import admission
        self.state = admission.requeue(
            self.state, jnp.asarray(sid), jnp.asarray(vals),
            jnp.asarray(ts), jnp.asarray(valid), jnp.asarray(tenant),
            jnp.asarray(its))
        self._sync_admitted()

    # ------------------------------------------------------------- readback
    def value_of(self, stream) -> np.ndarray:
        """Last stored value of ``stream`` — a host ``(channels,)`` f32
        array (zeros until the stream first emits)."""
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        return np.asarray(self.state.values[sid])

    def ts_of(self, stream) -> int:
        """Last emission timestamp of ``stream`` (``INT_MIN`` = never)."""
        sid = stream.sid if hasattr(stream, "sid") else int(stream)
        return int(self.state.timestamps[sid])

    def counters(self) -> Dict[str, int]:
        """The engine's scalar stat counters as a host dict (summed across
        shards on the sharded engine); keys are :data:`STAT_KEYS`."""
        return {k: int(v) for k, v in self.state.stats.items()}

    # ---------------------------------------------------------- elastic mesh
    def resize(self, n_shards: int, *, mesh=None,
               partition: Optional[str] = None) -> "StreamEngine":
        """Live shard scale-out/in at a superstep boundary.

        Re-shards the engine *in place* to ``n_shards`` and returns
        ``self`` — the object morphs between :class:`StreamEngine`
        (``n_shards == 1``) and the sharded engine, so every holder of the
        reference (serving bridge routes, autoscalers, user code) keeps a
        valid engine.  The mechanism is the durability plane: take a
        :meth:`snapshot`, re-shard its flat host arrays with
        :func:`repro.distributed.stream_sharding.reshard_snapshot` (rows,
        retention rings, queue contents and dead letters all migrate to
        their new owner shards), and install the result — so ``resize(M)``
        is *by construction* bit-identical to ``restore_engine(snapshot,
        n_shards=M)``, the primitive's oracle.

        The registry (and every Stream handle it issued) survives — only
        its ``cfg`` moves to the new shard count.  At most one retrace is
        paid per resize: the re-lowered round/superstep closure compiles on
        its first post-resize call, and a resize back to a previously
        visited layout re-uses the cached closure (zero recompilation);
        nothing else on the resize path traces.
        Caveats: per-tenant token buckets reset (quota refills resume next
        round), and scale-in can overflow the smaller per-shard queues —
        overflowed SUs are counted (``dropped_overflow``/``purged``) and
        dead-lettered, never silently lost."""
        n_shards = int(n_shards)
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if n_shards == self.cfg.n_shards and \
                (partition is None or partition == self.cfg.partition):
            return self
        from repro.distributed import stream_sharding as _sh
        arrays, meta = self.snapshot()
        arrays, meta = _sh.reshard_snapshot(arrays, meta, n_shards,
                                            partition=partition)
        new_cfg = EngineConfig(**meta["registry"]["cfg"]).validate()
        # keep the live registry object: user-held Stream handles (and the
        # serving bridge's routes) reference it by identity
        self.registry.cfg = new_cfg
        self.cfg = new_cfg
        if n_shards > 1:
            self.__class__ = _sh.ShardedStreamEngine
            self._bind_mesh(mesh)
            self.plan = None            # force a step re-lower in install
            self._install_snapshot(arrays, meta)
        else:
            self.__class__ = StreamEngine
            for attr in ("mesh", "plan", "gmap", "_shard", "_repl",
                         "_occupancy", "_spare", "_holes", "_ring_dirty"):
                self.__dict__.pop(attr, None)
            self._compiled_for(
                "single", lambda fused: make_step(self.cfg, self._fanout_fn,
                                                  fused=fused))
            self._install_snapshot(arrays, meta)
        return self


def create_engine(registry: Registry, *, mesh=None, **kw):
    """Build the engine matching ``registry.cfg``: a plain single-device
    :class:`StreamEngine` when ``cfg.n_shards == 1``, otherwise the
    sharded engine partitioned over a 1-D device mesh (see
    :mod:`repro.distributed.stream_sharding`)."""
    if registry.cfg.n_shards > 1:
        from repro.distributed.stream_sharding import ShardedStreamEngine
        return ShardedStreamEngine(registry, mesh=mesh, **kw)
    if mesh is not None:
        raise ValueError("mesh given but cfg.n_shards == 1; set "
                         "EngineConfig.n_shards to shard the stream plane")
    return StreamEngine(registry, **kw)


def restore_engine(source, *, step: Optional[int] = None, mesh=None,
                   fanout_fn: Callable = fanout_reference,
                   n_shards: Optional[int] = None,
                   partition: Optional[str] = None):
    """Rebuild a running engine from a snapshot — the recovery half of
    ``StreamEngine.snapshot()``.

    ``source`` is a checkpoint directory path, a
    :class:`~repro.checkpoint.ckpt.CheckpointManager`, or an in-memory
    ``(arrays, meta)`` pair.  The registry mirror in ``meta`` rebuilds the
    host control plane (including the exact :class:`EngineConfig`), the
    engine class is chosen by the snapshot's kind (single vs sharded), and
    tables/state/backlog are installed verbatim — the continuation is
    bit-identical to the uninterrupted run.  Returns ``None`` when no
    checkpoint exists yet (``step=None`` picks the newest).

    Cross-shard-count restore: ``n_shards``/``partition`` re-shard the
    snapshot before installing it, so an N-shard checkpoint restores into
    an M-shard engine (or a single-device one, ``n_shards=1``) — the same
    :func:`~repro.distributed.stream_sharding.reshard_snapshot` mapping
    ``StreamEngine.resize`` uses, which makes this path the resize
    primitive's differential oracle.

    Torn checkpoints: with ``step=None`` a corrupt newest checkpoint
    (checksum mismatch, truncated leaf) is *skipped*, falling back to the
    next older valid one — the contract the self-healing supervisor leans
    on.  An explicitly requested ``step`` still raises
    :class:`~repro.checkpoint.ckpt.CheckpointCorrupt` on damage."""
    if isinstance(source, tuple):
        arrays, meta = source
    else:
        from repro.checkpoint import ckpt as _ckpt
        if isinstance(source, _ckpt.CheckpointManager):
            if step is None:
                step, arrays, meta = source.load_latest()
                if step is None:
                    return None
            else:
                source.wait()
                arrays, meta = _ckpt.load(source.path, step)
        else:
            path = os.fspath(source)
            if step is None:
                step, arrays, meta = _ckpt.load_latest_valid(path)
                if step is None:
                    return None
            else:
                arrays, meta = _ckpt.load(path, step)
    if n_shards is not None or partition is not None:
        from repro.distributed.stream_sharding import reshard_snapshot
        cfg0 = EngineConfig(**meta["registry"]["cfg"])
        want = int(n_shards) if n_shards is not None else cfg0.n_shards
        if want != cfg0.n_shards or \
                (partition or cfg0.partition) != cfg0.partition:
            arrays, meta = reshard_snapshot(arrays, meta, want,
                                            partition=partition)
    registry = Registry.from_snapshot(meta["registry"])
    if meta.get("kind") == "sharded":
        from repro.distributed.stream_sharding import ShardedStreamEngine
        eng = ShardedStreamEngine(registry, mesh=mesh, fanout_fn=fanout_fn)
    else:
        eng = StreamEngine(registry, fanout_fn=fanout_fn)
    eng._install_snapshot(arrays, meta)
    return eng
