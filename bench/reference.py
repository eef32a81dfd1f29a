"""Plain reference of the pub/sub round: the same operations on the same
posted events, written from the semantics (paper Section IV, Listing 2)
and the deployment's stated capacities, importing nothing of the program.

A deployment is a :class:`Topology`: per stream row its input rows and
its transform (a plain Python function from the deployment's builder
module), the shard count and the block partition, and the
capacities that shape a round.  :func:`simulate` replays, superstep by
superstep, exactly the events the timed window posted, and returns every
external emission with the round it left in, the final last-value table,
and the drops.

One round, per the semantics the configuration states:

1. *Ingest.*  Each superstep boundary assigns pending events to its K
   rounds in post order: at most one event per stream and at most
   ``batch`` events per round; the rest wait.  An ingested event is kept
   if its timestamp is newer than its stream's last one, stored as the
   stream's last value, and appended to its shard's FIFO queue.
2. *Pop and dispatch.*  Each shard pops the ``batch`` oldest queued
   events; each becomes one work item per subscriber (subscribers in
   stream-row order), routed to the shard owning the subscriber.  A
   shard receives items in (sending shard, sending order) order.
3. *Fetch and transform.*  Against the last values after this round's
   ingest: the trigger input takes the event's value and timestamp, the
   other inputs their last value; the transform runs; the item is kept
   if its timestamp is newer than the target's last emission and its
   filters pass.  Its output timestamp is the largest of the trigger's,
   the target's last and the other inputs'.
4. *Store and emit.*  Per target, the kept item with the newest output
   timestamp wins (ties: lower trigger row, then earlier item).  Winners
   are stored, emitted to the sink, and queued again when they have
   subscribers.  A queue that is full drops the event (counted).

Values are float32 throughout (``precision="float32"``); with
``precision="bfloat16"`` every stored and computed value is rounded to
bfloat16 instead, which is the control the comparison must reject.
"""
from __future__ import annotations

import collections
import dataclasses
import struct
from typing import Callable, List, Optional, Sequence

import numpy as np

INT_MIN = -(2 ** 31) + 1          # "never emitted"


def f32(x: float) -> float:
    """Round a Python float to the nearest float32 (an exact float32
    add, subtract, multiply or divide is the double result rounded once,
    since a double holds more than twice float32's precision)."""
    return struct.unpack("f", struct.pack("f", x))[0]


def bf16(x: float) -> float:
    """Round to the nearest bfloat16, ties to even."""
    b = struct.unpack("I", struct.pack("f", x))[0]
    b = ((b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000) & 0xFFFFFFFF
    return struct.unpack("f", struct.pack("I", b))[0]


ROUNDING = {"float32": f32, "bfloat16": bf16}

# transform(ins, prev, q) -> (channel values, passes): ``ins`` holds one
# channel tuple per input slot, ``prev`` the target's own last value, and
# ``q`` rounds one arithmetic result to the working precision.
Transform = Callable[[Sequence[tuple], tuple, Callable[[float], float]],
                     tuple]


@dataclasses.dataclass
class Topology:
    n_streams: int
    n_shards: int
    batch: int            # per-round ingest selection and per-shard pop
    queue: int            # per-shard queue capacity
    channels: int
    inputs: List[List[int]]                 # per row: input rows
    transforms: List[Optional[Transform]]   # per row: None = source/unused

    def subscribers(self) -> List[List[int]]:
        out: List[List[int]] = [[] for _ in range(self.n_streams)]
        for sid, ins in enumerate(self.inputs):
            for src in ins:
                if sid not in out[src]:
                    out[src].append(sid)
        return out

    def shard_of(self) -> np.ndarray:
        n_local = -(-self.n_streams // self.n_shards)
        return np.arange(self.n_streams) // n_local


@dataclasses.dataclass
class Result:
    records: dict          # round, sid, ts, its: (n,) ints; vals: (n, C) f32
    values: np.ndarray     # (N, C) float32 final last values
    timestamps: np.ndarray  # (N,) int64 final last timestamps
    dropped: int


def simulate(topo: Topology, posted: Sequence[tuple], K: int,
             precision: str = "float32") -> Result:
    """Replay ``posted`` — one ``(sid, vals, ts, its)`` tuple of arrays
    per superstep, in post order — through K-round supersteps."""
    q = ROUNDING[precision]
    N, C, B, S = topo.n_streams, topo.channels, topo.batch, topo.n_shards
    zero = (0.0,) * C
    subs = topo.subscribers()
    shard = topo.shard_of().tolist()
    inputs, transforms = topo.inputs, topo.transforms
    values: List[tuple] = [zero] * N
    stamps: List[int] = [INT_MIN] * N
    queues = [collections.deque() for _ in range(S)]
    pending: List[list] = []
    dropped = 0
    rec = {k: [] for k in ("round", "sid", "ts", "its", "vals")}

    def enqueue(s: int, item: tuple) -> None:
        nonlocal dropped
        if len(queues[s]) < topo.queue:
            queues[s].append(item)
        else:
            dropped += 1

    for step, (p_sid, p_vals, p_ts, p_its) in enumerate(posted):
        p_vals = np.asarray(p_vals, np.float32).reshape(len(p_sid), -1) \
            if len(p_sid) else np.zeros((0, C), np.float32)
        for i in range(len(p_sid)):
            v = [q(float(x)) for x in p_vals[i]] + [0.0] * C
            pending.append([int(p_sid[i]), tuple(v[:C]), int(p_ts[i]),
                            int(p_its[i])])
        for k in range(K):
            # ---- ingest: one event per stream, `batch` per round -------
            take, rest, seen = [], [], set()
            for e in pending:
                if len(take) < B and e[0] not in seen:
                    take.append(e)
                    seen.add(e[0])
                else:
                    rest.append(e)
            pending = rest
            for sid, v, ts, its in take:
                if ts > stamps[sid]:
                    values[sid], stamps[sid] = v, ts
                    enqueue(shard[sid], (sid, v, ts, its))
            # ---- pop and dispatch, per shard ---------------------------
            recv: List[list] = [[] for _ in range(S)]
            for s in range(S):
                qs = queues[s]
                for _ in range(min(B, len(qs))):
                    sid, v, ts, its = qs.popleft()
                    for t in subs[sid]:
                        recv[shard[t]].append((t, sid, v, ts, its))
            # ---- fetch, transform, gate: against the post-ingest values
            results = []
            for s in range(S):
                best = {}
                for j, (t, src, v, ts, its) in enumerate(recv[s]):
                    fn = transforms[t]
                    if fn is None:
                        continue
                    prev_ts = stamps[t]
                    ins, ts_out = [], max(ts, prev_ts)
                    trig = inputs[t].index(src) if src in inputs[t] else 0
                    for m, r in enumerate(inputs[t]):
                        if m == trig:
                            ins.append(v)
                        else:
                            ins.append(values[r])
                            ts_out = max(ts_out, stamps[r])
                    out, passes = fn(ins, values[t], q)
                    out = tuple(out) + zero[len(out):]
                    if not (ts > prev_ts and passes):
                        continue
                    key = (-ts_out, src, j)
                    if t not in best or key < best[t][0]:
                        best[t] = (key, (t, out, ts_out, its))
                # winners in work-item order: the order they queue again
                results.append([w for _, w in
                                sorted(best.values(), key=lambda b: b[0][2])])
            # ---- store, emit, queue again ------------------------------
            rnd = step * K + k
            for s in range(S):
                for t, out, ts_out, its in results[s]:
                    values[t], stamps[t] = out, ts_out
                    rec["round"].append(rnd)
                    rec["sid"].append(t)
                    rec["ts"].append(ts_out)
                    rec["its"].append(its)
                    rec["vals"].append(out)
                    if subs[t]:
                        enqueue(s, (t, out, ts_out, its))
    records = {k: np.asarray(rec[k], np.int64)
               for k in ("round", "sid", "ts", "its")}
    records["vals"] = np.asarray(rec["vals"], np.float32).reshape(-1, C)
    return Result(records, np.asarray(values, np.float32).reshape(N, C),
                  np.asarray(stamps, np.int64), dropped)


def window_aggregates(records: dict, sids: np.ndarray, window: int,
                      upto_rounds: Sequence[int]) -> List[dict]:
    """Last-``window``-emissions aggregates of each stream in ``sids``
    (channel 0), as read after each round count in ``upto_rounds``:
    sum, mean, max, min and count, computed in float64."""
    sids = np.asarray(sids, np.int64)
    keep = np.isin(records["sid"], sids)
    r_round = records["round"][keep].astype(np.int64)
    r_sid = records["sid"][keep].astype(np.int64)
    r_val = records["vals"][keep, 0].astype(np.float64)
    # one sorted key per record: stream, then round
    span = int(max(r_round.max(initial=0), max(upto_rounds, default=0))) + 1
    order = np.lexsort((r_round, r_sid))
    key, r_val = r_sid[order] * span + r_round[order], r_val[order]
    lo = np.searchsorted(key, sids * span, side="left")
    back = np.arange(-window, 0)
    out = []
    for upto in upto_rounds:
        # each stream's emissions before `upto`: the last `window` of them
        n = np.searchsorted(key, sids * span + upto, side="left")
        idx = n[:, None] + back[None, :]
        have = idx >= lo[:, None]
        x = np.where(have, np.append(r_val, 0.0)[np.maximum(idx, 0)], 0.0)
        count = have.sum(axis=1).astype(np.float64)
        some = count > 0
        s = x.sum(axis=1)
        out.append({
            "sum": s,
            "mean": np.where(some, s / np.maximum(count, 1.0), 0.0),
            "max": np.where(some, np.where(have, x, -np.inf).max(axis=1), 0.0),
            "min": np.where(some, np.where(have, x, np.inf).min(axis=1), 0.0),
            "count": count,
            "abs": np.abs(x).sum(axis=1),
        })
    return out
