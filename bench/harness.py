"""Run one cell once: build the deployment, warm its programs, drive the
measured window, drain, then compare against the plain reference.

The window drives the engine as its users do: ``StreamEngine.post`` for
each due event (stamped with its scheduled creation time through
``its=``), ``StreamEngine.superstep``, ``StreamEngine.spool_sinks``, and
for STATS tenants ``WindowedStats.push_sink`` for each round's sink and
``WindowedStats.aggregates`` at every window boundary.  A record counts as
received once the host holds that superstep's decoded sink records.

Each call into a layer is a host span (``jax.profiler.TraceAnnotation``
named ``bench.<layer>``), so a traced run can charge the device's idle
gaps to what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, reference, spec, tracing

DRAIN_CAP_S = 60.0         # an answer may come this long after posting ends
BACKLOG_EVERY_S = 0.5
STALL_MS = 100.0           # a superstep this long is listed in diagnostics
TRACE_FROM_S = 2.0         # a traced run traces this far into its window
TRACE_LEN_S = 3.0          # for this long


class Spans:
    """Host spans around each call into a layer: a profiler annotation,
    and the seconds spent, summed by name while ``on``."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.on = False
        self.total: Dict[str, float] = {}
        self.step: Dict[str, float] = {}     # this superstep's spans

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with self._annotation("bench." + name):
            yield
        dt = time.perf_counter() - t
        self.step[name] = self.step.get(name, 0.0) + dt
        if self.on:
            self.total[name] = self.total.get(name, 0.0) + dt


@dataclasses.dataclass
class RunRecord:
    """What the readers (``bench/readers``) take their numbers from."""
    setup_s: float
    seconds: float
    latency_ms: np.ndarray        # terminal records of the window's events
    delivered: int                # terminal records received in the window
    late_ms: np.ndarray           # post time minus scheduled time
    params: dict                  # the configuration's sizes
    device_kind: str
    seg: Optional[dict] = None    # traced segment: spans and counts
    trace: Optional[dict] = None  # tracing.reduce of the traced segment


@dataclasses.dataclass
class Outcome:
    record: RunRecord
    attempted: int
    failed: int
    checks: Dict[str, dict]
    correct: bool
    device: dict
    diagnostics: dict
    posted: list = dataclasses.field(default_factory=list)
    backlog: list = dataclasses.field(default_factory=list)
    agg_rounds: list = dataclasses.field(default_factory=list)
    stats_sids: np.ndarray = None


def _final_state(eng):
    values = np.asarray(eng.state.values)
    stamps = np.asarray(eng.state.timestamps)
    plan = getattr(eng, "plan", None)
    if plan is not None and values.ndim == 3:
        values = values.reshape(-1, values.shape[-1])[plan.sid_to_flat]
        stamps = stamps.reshape(-1)[plan.sid_to_flat]
    return values, stamps.astype(np.int64)


def _dropped(counters: dict) -> int:
    return sum(v for k, v in counters.items() if k.startswith("dropped_"))


def _queued(eng) -> int:
    return int(np.asarray(eng.state.tenant_queued).sum())


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, trace_dir: Optional[str] = None,
             keep_trace: bool = False, compare: bool = True,
             traffic_params: Optional[dict] = None,
             pause: Optional[tuple] = None) -> Outcome:
    """One run of ``cell``.  ``pause`` = (at_s, seconds) holds the host
    that long once, that far into the window, as a host stall would: the
    sweep uses it to find the rate the engine sustains through one."""
    import jax
    from repro.launch.compiles import compile_count

    params = cell.config["params"]
    builder = spec.kind("builders", cell.config["builder"])
    mix = dict(cell.traffic["params"], **(traffic_params or {}))
    generator = spec.kind("traffic", cell.traffic["generator"])
    devs = jax.devices()[:cell.chips]
    spans = Spans()

    # ---- set-up: build, warm every program the window runs ------------
    dep = builder.build(params, cell.chips)
    eng, K = dep.engine, dep.K
    gen = generator.make(mix, len(dep.source_sids), seconds, seed)
    for _ in range(2):
        spool = eng.superstep(K)
        jax.block_until_ready(spool)
        sinks = eng.spool_sinks(spool)
        if dep.stats is not None:
            dep.stats.push_sink(sinks[0])
            {k: np.asarray(v) for k, v in dep.stats.aggregates().items()}
        _queued(eng)
    base_counters = eng.counters()
    # Set-up leaves a large heap (registry, tables, traced programs):
    # collect it now and keep it out of the window's collections, which
    # then scan only what the window allocates.
    gc.collect()
    gc.freeze()
    step_s: List[float] = []
    stalls: List[dict] = []

    N = int(eng.cfg.n_streams)
    is_term = np.zeros(N, bool)
    is_term[dep.terminal_sids] = True
    is_stats = np.zeros(N, bool)
    is_stats[dep.stats_sids] = True
    src = dep.source_sids
    # a closed loop posts its next cycle only while fewer than one
    # superstep's ingest (K rounds of `batch`) waits on the host
    hold = K * int(eng.cfg.batch)
    window_us = seconds * 1e6
    post_end_us = window_us + gen.tail_s * 1e6
    trace_from_us = min(TRACE_FROM_S, seconds / 4) * 1e6
    trace_to_us = trace_from_us + min(TRACE_LEN_S, seconds / 2) * 1e6
    if trace:
        # stopping the profiler holds the host for many seconds; an open
        # loop would meet that as a backlog, so a traced run posts only
        # until its segment ends
        post_end_us = min(post_end_us, trace_to_us)
    tracing_now = False
    seg = None

    posted: List[tuple] = []
    recs: List[tuple] = []
    lat, late = [], []
    delivered = attempted = 0
    aggs: List[dict] = []
    agg_rounds: List[int] = []
    backlog: List[tuple] = []
    compiles_window = None
    if trace:
        # the profiler starts in set-up: its start can hold the host for
        # a second or more, which the open loop would meet as a backlog
        jax.profiler.start_trace(trace_dir)
    profiling = trace
    c0 = compile_count()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    step = 0
    next_sample = 0.0
    drain_from = None

    def now_us() -> float:
        return (time.perf_counter() - t0) * 1e6

    cpu = [time.process_time(), time.thread_time()]
    while True:
        if pause is not None and now_us() >= pause[0] * 1e6:
            time.sleep(pause[1])
            pause = None
        t_us = now_us()
        cpu_now = [time.process_time(), time.thread_time()]
        if step_s and t_us - step_s[-1] > STALL_MS * 1e3 and \
                len(stalls) < 20:
            stalls.append({"at_s": step_s[-1] * 1e-6,
                           "ms": (t_us - step_s[-1]) * 1e-3,
                           "cpu_ms": (cpu_now[0] - cpu[0]) * 1e3,
                           "thread_ms": (cpu_now[1] - cpu[1]) * 1e3,
                           **{k: v * 1e3 for k, v in spans.step.items()}})
        cpu = cpu_now
        spans.step = {}
        step_s.append(t_us)
        if trace and not tracing_now and seg is None and \
                t_us >= trace_from_us:
            spans.on, spans.total, tracing_now = True, {}, True
            seg_start = (step, len(aggs))
            seg_cm = jax.profiler.TraceAnnotation(tracing.SEGMENT)
            seg_cm.__enter__()
        elif tracing_now and t_us >= trace_to_us:
            seg_cm.__exit__(None, None, None)
            spans.on, tracing_now = False, False
            seg = {"spans": dict(spans.total),
                   "supersteps": step - seg_start[0],
                   "rounds": (step - seg_start[0]) * K,
                   "window_reads": len(aggs) - seg_start[1]}
            jax.profiler.stop_trace()
            profiling = False
        if compiles_window is None and t_us >= window_us:
            compiles_window = compile_count() - c0
        if t_us < post_end_us:
            with spans("post"):
                if gen.closed and len(eng._pending) >= hold:
                    dev = np.zeros(0, np.int64)
                    vals, sched = np.zeros(0, np.float32), dev
                else:
                    dev, vals, sched = gen.due(t_us)
                ts = gen.ts(sched, step)
                sids = src[dev]
                for i in range(len(dev)):
                    eng.post(int(sids[i]), [float(vals[i])], ts=int(ts[i]),
                             its=int(sched[i]))
            in_win = sched < window_us
            attempted += int(in_win.sum())
            if tracing_now or not trace:   # a traced run: its segment
                late.append((t_us - sched[in_win]) * 1e-3)
            posted.append((sids, vals, ts, sched))
        else:
            if drain_from is None:
                drain_from = time.perf_counter()
            posted.append((np.zeros(0, np.int64),) * 4)
        with spans("superstep"):
            spool = eng.superstep(K)
        with spans("device_wait"):
            jax.block_until_ready(spool)
        with spans("readback"):
            sinks = eng.spool_sinks(spool)
            got = []
            for k, b in enumerate(sinks):
                m = np.asarray(b.valid).reshape(-1)
                sid = np.asarray(b.sid).reshape(-1)[m]
                got.append((step * K + k, sid,
                            np.asarray(b.ts).reshape(-1)[m],
                            np.asarray(b.its).reshape(-1)[m],
                            np.asarray(b.vals).reshape(m.shape[0], -1)[m]))
        t_recv = now_us()
        for rnd, sid, ts_, its, vals_ in got:
            recs.append((rnd, sid, ts_, its, vals_))
            term = is_term[sid]
            t_its = its[term]
            lat.append((t_recv - t_its[t_its < window_us]) * 1e-3)
            if t_recv <= window_us:
                delivered += int(term.sum())
        if dep.stats is not None:
            with spans("stats_fold"):
                for b in sinks:
                    keep = is_stats[np.asarray(b.sid).reshape(-1)] \
                        & np.asarray(b.valid).reshape(-1)
                    dep.stats.push_sink(type(b)(
                        b.sid, b.vals, b.ts,
                        keep.reshape(np.asarray(b.valid).shape), b.its))
        step += 1
        if dep.stats is not None and (step * K) % dep.window_rounds == 0:
            with spans("window_read"):
                agg = dep.stats.aggregates()
                aggs.append({k: np.asarray(v)[dep.stats_sids, 0]
                             for k, v in agg.items()})
            agg_rounds.append(step * K)
        if t_us >= next_sample or drain_from is not None:
            q = _queued(eng)
            backlog.append((t_us * 1e-6, len(eng._pending) + q))
            next_sample = t_us + BACKLOG_EVERY_S * 1e6
            if drain_from is not None and not eng._pending and q == 0:
                break
            if drain_from is not None and \
                    time.perf_counter() - drain_from > DRAIN_CAP_S:
                break
    t_end = time.perf_counter()
    gc.unfreeze()
    if compiles_window is None:
        compiles_window = compile_count() - c0
    compiles_run = compile_count() - c0
    if tracing_now:                       # window shorter than the segment
        seg_cm.__exit__(None, None, None)
        seg = {"spans": dict(spans.total), "supersteps": step - seg_start[0],
               "rounds": (step - seg_start[0]) * K,
               "window_reads": len(aggs) - seg_start[1]}
    if profiling:
        jax.profiler.stop_trace()

    # ---- after the window: device facts, then free the program --------
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    end_counters = eng.counters()
    failed = _dropped(end_counters) - _dropped(base_counters)
    emitted = int(end_counters["emitted"]) - int(base_counters["emitted"])
    values, stamps = _final_state(eng)
    path, stats_sids = dep.path, dep.stats_sids
    dep = eng = spool = sinks = None
    gc.collect()

    red = None
    if trace:
        red = tracing.reduce(tracing.load(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    prog = {
        "round": np.concatenate([np.full(r[1].shape, r[0]) for r in recs]),
        "sid": np.concatenate([r[1] for r in recs]),
        "ts": np.concatenate([r[2] for r in recs]),
        "its": np.concatenate([r[3] for r in recs]),
        "vals": np.concatenate([r[4] for r in recs]).astype(np.float32),
    }
    record = RunRecord(
        setup_s=setup_s, seconds=seconds,
        latency_ms=np.concatenate(lat) if lat else np.zeros(0),
        delivered=delivered,
        late_ms=np.concatenate(late) if late else np.zeros(0),
        params=params, device_kind=devs[0].device_kind, seg=seg, trace=red)
    checks, correct, ref_s, ref_dropped = {}, False, 0.0, None
    if compare:
        t_ref = time.perf_counter()
        topo = builder.topology(params)
        ref = reference.simulate(topo, posted, K)
        prog_aggs = ref_aggs = None
        if aggs:
            prog_aggs = aggs
            ref_aggs = reference.window_aggregates(
                ref.records, stats_sids, params["window"], agg_rounds)
        checks = check.compare(prog, values, stamps, failed, ref,
                               prog_aggs, ref_aggs)
        correct = check.passed(checks, len(prog["sid"]))
        ref_dropped = ref.dropped
        ref_s = time.perf_counter() - t_ref
    backlog_arr = np.asarray(backlog) if backlog else np.zeros((0, 2))
    in_win = backlog_arr[backlog_arr[:, 0] < seconds]
    n5 = max(1, len(in_win) // 5)
    diagnostics = {
        "round_path": path,
        "compiles_in_window": compiles_window,
        "compiles_in_run": compiles_run,
        "supersteps": step,
        "records": int(len(prog["sid"])),
        "latency_samples": int(record.latency_ms.size),
        "latency_pct_ms": {q: float(np.percentile(record.latency_ms, q))
                           for q in (90, 95, 99, 99.9)}
        if record.latency_ms.size else {},
        "backlog_first_fifth": float(np.median(in_win[:n5, 1]))
        if len(in_win) else 0.0,
        "backlog_last_fifth": float(np.median(in_win[-n5:, 1]))
        if len(in_win) else 0.0,
        # the engine's own count of emissions less the records its sink
        # spool delivered: 0 unless the sink dropped some unreported
        "emitted_not_spooled": emitted - int(len(prog["sid"])),
        "drain_s": 0.0 if drain_from is None else t_end - drain_from,
        "reference_s": ref_s,
        # what the reference's bounded queue drops of the same events
        "reference_dropped": ref_dropped,
        "superstep_ms_max": float(np.diff(step_s).max() * 1e-3)
        if len(step_s) > 1 else 0.0,
        "superstep_ms_p99": float(np.percentile(np.diff(step_s), 99) * 1e-3)
        if len(step_s) > 1 else 0.0,
        "stalls": stalls,
    }
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    return Outcome(record, attempted, failed, checks, correct, device,
                   diagnostics, posted, backlog, agg_rounds, stats_sids)
