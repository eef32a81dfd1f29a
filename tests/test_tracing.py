"""The program's own trace: host spans (``repro.*``) around each call into
a layer, named scopes on the round's stages, and the benchmark's
reduction of both (``bench/program_trace.py``) — on the CPU under
``jax.profiler``, on hand-made traces, and on traces recorded from
``iot1k.live`` runs on a TPU v5e chip.  Also the ``sink_overflow``
counter: stage-4 winners a round's sink buffer leaves out."""
import copy
import json
import os
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program_trace, spec, tracing           # noqa: E402
from bench.harness import RunRecord                      # noqa: E402
from repro.core import EngineConfig, Registry, create_engine  # noqa: E402
from repro.distributed.stream_sharding import reshard_snapshot  # noqa: E402
from repro.workloads import build_suite                  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
SPANS = ("repro.stage", "repro.dispatch", "repro.spool.read",
         "repro.spool.decode", "repro.stats.push", "repro.stats.upload",
         "repro.stats.aggregate")
LOOP_SCOPES = {"ring_grid", "round_loop", "spool_append"}
FUSED_SCOPES = LOOP_SCOPES | {"ingest", "round_fuse", "pop_accounting",
                              "store_emit", "fault"}
STAGED_SCOPES = LOOP_SCOPES | {"ingest", "pop_accounting", "fanout", "apply",
                               "store_emit", "fault"}
SHARD_SCOPES = STAGED_SCOPES | {"snapshot", "exchange"}


def read(metric, rec):
    f = spec.metric_file(metric)
    return spec.kind("readers", f["reader"]).read(rec, f.get("args", {}))


def record(**kw):
    base = dict(setup_s=1.0, seconds=1.0, latency_ms=np.zeros(0),
                delivered=0, late_ms=np.zeros(0), params={},
                device_kind="TPU v5 lite")
    return RunRecord(**{**base, **kw})


# --------------------------------------------------------------------------
# the engine under the profiler, on the CPU
# --------------------------------------------------------------------------

def _traced_suite(tmp_path, n_shards=1, fused=True, steps=3):
    """A small IoT suite driven as the benchmark harness drives it, inside
    ``bench.*`` spans and a ``bench.segment``; returns the loaded trace
    and what the engine returned, per superstep."""
    suite = build_suite(8, kinds=("etl", "stats"), n_shards=n_shards,
                        slo_rounds=None, window=8, batch=16, queue=256,
                        fused_round=fused,
                        cfg_overrides={"superstep": 4, "sink_buffer": 16})
    eng, stats = suite.engine, suite.stats
    src = [f.source.sid for f in suite.flows]
    seen = []

    def step(i):
        for j, s in enumerate(src):
            eng.post(s, [float(i + j)], ts=i * 10 + 1)
        with jax.profiler.TraceAnnotation("bench.superstep"):
            spool = eng.superstep(4)
        jax.block_until_ready(spool)
        with jax.profiler.TraceAnnotation("bench.readback"):
            sinks = eng.spool_sinks(spool)
        with jax.profiler.TraceAnnotation("bench.stats_fold"):
            for b in sinks:
                stats.push_sink(b)
        with jax.profiler.TraceAnnotation("bench.window_read"):
            jax.block_until_ready(stats.aggregates())
        seen.append({"records": int(np.asarray(spool.fill).sum()),
                     "rows": [int(np.asarray(b.valid).sum())
                              for b in sinks]})

    step(0)
    step(1)                               # compiled and warm
    seen.clear()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.SEGMENT):
        for i in range(2, 2 + steps):
            step(i)
    jax.profiler.stop_trace()
    return program_trace.load(str(tmp_path)), seen, len(src)


def _host(trace):
    return [ev for p in trace["planes"]
            if not tracing.is_device_plane(p["name"])
            for ln in p["lines"] for ev in ln["events"]]


def _inside(ev, spans):
    return any(s[1] <= ev[1] and ev[1] + ev[2] <= s[1] + s[2]
               for s in spans)


def test_program_spans_nest_in_harness_spans(tmp_path):
    trace, seen, n_src = _traced_suite(tmp_path)
    host = _host(trace)
    bench = [ev for ev in host if ev[0].startswith("bench.")
             and ev[0] != tracing.SEGMENT]
    prog = [ev for ev in host if ev[0].startswith(program_trace.PREFIX)]
    by = {name: [ev for ev in prog if ev[0] == name] for name in SPANS}
    steps = len(seen)
    assert all(by[name] for name in SPANS), {k: len(v) for k, v in by.items()}
    for ev in prog:                       # every program span in a harness one
        assert _inside(ev, bench), ev
    for ev in by["repro.stats.upload"]:
        assert _inside(ev, by["repro.stats.push"])
    # one span per call: a superstep stages and dispatches once, reads its
    # spool once, and folds each of its four rounds
    assert len(by["repro.stage"]) == len(by["repro.dispatch"]) == steps
    assert len(by["repro.spool.read"]) == steps
    assert len(by["repro.spool.decode"]) == steps
    assert len(by["repro.stats.push"]) == len(by["repro.stats.upload"]) == \
        4 * steps
    assert len(by["repro.stats.aggregate"]) == steps
    # counts: every source posts once a superstep, all fit the grid
    for ev in by["repro.stage"]:
        assert ev[3] == {"sus": n_src, "shipped": n_src, "carried": 0}
    assert [ev[3]["records"] for ev in by["repro.spool.read"]] == \
        [s["records"] for s in seen]
    assert [ev[3]["rows"] for ev in by["repro.stats.push"]] == \
        [r for s in seen for r in s["rows"]]


def test_program_reduction_of_an_engine_trace(tmp_path):
    trace, seen, n_src = _traced_suite(tmp_path)
    # the CPU has no device plane: stand one op in for the chip
    seg = next(ev for ev in _host(trace) if ev[0] == tracing.SEGMENT)
    trace["planes"].append({"name": "/device:TPU:0", "lines": [
        {"name": tracing.OPS_LINE,
         "events": [["jit_superstep/fusion/f", seg[1], 1000.0,
                     "jit(superstep)/while/body/closed_call/ingest/add"]]}]})
    red = program_trace.reduce(trace)
    steps = len(seen)
    assert set(SPANS) <= set(red["program_span_s"])
    assert red["program_span_calls"]["repro.stage"] == steps
    assert red["program_span_calls"]["repro.stats.push"] == 4 * steps
    assert red["program_span_args"]["repro.stage"] == {
        "sus": steps * n_src, "shipped": steps * n_src, "carried": 0}
    assert red["program_span_args"]["repro.spool.read"]["records"] == \
        sum(s["records"] for s in seen)
    assert red["scope_s"] == {"ingest": pytest.approx(1e-6)}
    assert sum(red["idle_by_program_span_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    # nested spans take the gap: the push outside its upload, never both
    push = red["program_span_s"]["repro.stats.push"]
    upload = red["program_span_s"]["repro.stats.upload"]
    idle = red["idle_by_program_span_s"]
    assert idle["repro.stats.upload"] == pytest.approx(upload)
    assert idle["repro.stats.push"] == pytest.approx(push - upload)
    bd = program_trace.breakdown(red)
    assert {"device_ops", "idle_gaps", "idle_gaps_program"} <= set(bd)


@pytest.mark.parametrize("n_shards,fused,scopes", [
    (1, True, FUSED_SCOPES), (1, False, STAGED_SCOPES),
    (4, True, SHARD_SCOPES)], ids=["fused", "staged", "sharded4"])
def test_round_stages_carry_named_scopes(tmp_path, n_shards, fused, scopes):
    """Every stage scope is in the superstep program's op metadata, read
    from the profile's HLO protos, and the sharded engine writes the
    same host spans."""
    if len(jax.devices()) < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    trace, seen, n_src = _traced_suite(tmp_path, n_shards, fused, steps=1)
    names = {ev[0] for ev in _host(trace)}
    assert set(SPANS) <= names
    xplane = open(program_trace._newest(str(tmp_path)), "rb").read()
    hlo = program_trace.hlo_op_names(xplane)
    prog = "jit_shard_superstep" if n_shards > 1 else "jit_superstep"
    found = {program_trace.innermost_scope(p) for p in hlo[prog].values()}
    assert scopes <= found, scopes - found


# --------------------------------------------------------------------------
# the reduction on hand-made traces
# --------------------------------------------------------------------------

def hand_trace():
    """Two chips over a 400 ns segment; harness spans with program spans
    nested inside them, and device ops with scope paths."""
    k = "jit_superstep/custom-call:tpu_custom_call/k"
    root = "jit(superstep)/while/body/closed_call/"
    dev0 = [["jit_superstep/while/w", 0, 100, "jit(superstep)/while"],
            [k, 10, 30, root + "round_fuse/fused_round/pallas_call"],
            ["jit_superstep/fusion/f", 50, 10, root + "store_emit/scatter"],
            ["jit_window_agg_op/custom-call:tpu_custom_call/a", 200, 50, ""]]
    dev1 = [[k, 0, 30, root + "round_fuse/fused_round/pallas_call"],
            ["jit_superstep/fusion/f", 300, 100,
             root + "ingest/jit(cumsum)/_tenant_rank/cumsum"]]
    host = [["bench.segment", 0, 400], ["bench.post", 100, 100],
            ["bench.readback", 250, 150]]
    prog = [["repro.spool.read", 260, 90, {"records": 7}],
            ["repro.spool.decode", 350, 20, {}],
            ["repro.stats.push", 100, 80, {"rows": 3}],
            ["repro.stats.upload", 120, 40, {}],
            ["repro.stats.push", 390, 30, {"rows": 2}]]   # ends after it
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": dev0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops",
                                             "events": dev1}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": prog}]}]}


def _without_program(trace):
    out = copy.deepcopy(trace)
    for p in out["planes"]:
        for ln in p["lines"]:
            ln["events"] = [ev[:3] for ev in ln["events"]
                            if not ev[0].startswith("repro.")]
    return out


def test_program_spans_leave_the_harness_numbers_alone():
    trace = hand_trace()
    red = program_trace.reduce(trace)
    base = tracing.reduce(_without_program(trace))
    assert {k: red[k] for k in base} == base
    assert red["idle_by_span_s"]["post"] == pytest.approx(100e-9)


def test_idle_gaps_go_to_the_innermost_program_span():
    red = program_trace.reduce(hand_trace())
    # chip 0 gaps [100,200] and [250,400]; chip 1 [30,300]; per chip
    idle = {k: v * 2e9 for k, v in red["idle_by_program_span_s"].items()}
    assert idle["repro.stats.upload"] == pytest.approx(40 + 40)
    assert idle["repro.stats.push"] == pytest.approx(40 + 40 + 10)
    assert idle["repro.spool.read"] == pytest.approx(90 + 40)
    assert idle["repro.spool.decode"] == pytest.approx(20)
    assert idle[program_trace.NO_SPAN] == pytest.approx(
        20 + 10 + 20 + 70 + 80)
    assert sum(idle.values()) == pytest.approx(
        2e9 * (red["window_s"] - red["busy_s"]))
    assert red["program_span_s"]["repro.stats.push"] == pytest.approx(90e-9)
    assert red["program_span_calls"] == {
        "repro.spool.read": 1, "repro.spool.decode": 1,
        "repro.stats.push": 2, "repro.stats.upload": 1}
    assert red["program_span_args"]["repro.stats.push"] == {"rows": 5}
    assert program_trace.breakdown(red)["idle_gaps_program"][0][0] == \
        program_trace.NO_SPAN


def test_scope_time_sums_device_ops_by_innermost_scope():
    red = program_trace.reduce(hand_trace())
    scope = {k: v * 2e9 for k, v in red["scope_s"].items()}
    # the while encloses ops: busy time, not an op, as in op_s
    assert scope == {"round_fuse": pytest.approx(60),
                     "store_emit": pytest.approx(10),
                     "ingest": pytest.approx(100),
                     program_trace.NO_SCOPE: pytest.approx(50)}
    seg = {"spans": {}, "supersteps": 2, "rounds": 8, "window_reads": 1}
    rec = record(trace=red, seg=seg)
    assert read("ingest_dev_ms.live", rec) == pytest.approx(50e-9 / 8 * 1e3)
    assert read("store_emit_ms.live", rec) == pytest.approx(5e-9 / 8 * 1e3)
    assert read("spool_read_ms.live", rec) == pytest.approx(90e-9 / 2 * 1e3)
    assert read("stats_upload_ms.live", rec) == pytest.approx(40e-9 / 2 * 1e3)
    assert read("stage_ms.live", rec) is None             # no such span
    assert read("ingest_dev_ms.live", record(trace=tracing.reduce(
        _without_program(hand_trace())), seg=seg)) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(superstep)/while/body/closed_call/ingest/jit(cumsum)/"
     "_tenant_rank/reduce_window_sum", "ingest"),
    ("jit(superstep)/while/body/closed_call/round_fuse/while/body/vmap()/add",
     "round_fuse"),
    ("jit(superstep)/ring_grid/jit(_where)/select_n", "ring_grid"),
    ("jit(shard_superstep)/shard_map/while/body/closed_call/exchange/"
     "all_to_all", "exchange"),
    ("jit(superstep)/round_loop/while/body/closed_call/round_fuse/"
     "fused_round/pallas_call", "round_fuse"),     # a kernel's name: no scope
    ("jit(superstep)/round_loop/while/body/squeeze", "round_loop"),
    ("jit(superstep)/while", program_trace.NO_SCOPE),
    ("reduce_sum", program_trace.NO_SCOPE)])
def test_innermost_scope_of_an_op_name(op_name, scope):
    assert program_trace.innermost_scope(op_name) == scope


def test_program_span_reader_per_argument():
    red = {"program_span_s": {"repro.stage": 0.002},
           "program_span_args": {"repro.stage": {"sus": 100}}}
    seg = {"spans": {}, "supersteps": 4, "rounds": 16, "window_reads": 2}
    rec = record(trace=red, seg=seg)
    assert read("stage_ms.live", rec) == pytest.approx(0.5)
    assert read("stage_us_per_su.live", rec) == pytest.approx(20.0)
    red["program_span_args"]["repro.stage"]["sus"] = 0
    assert read("stage_us_per_su.live", rec) is None


# --------------------------------------------------------------------------
# recorded traces from iot1k.live on a TPU v5e chip
# --------------------------------------------------------------------------

def test_reduction_of_the_first_recorded_trace_is_unchanged():
    with open(os.path.join(DATA, "trace_v5e_iot1k_live.json")) as f:
        trace = json.load(f)
    red = program_trace.reduce(trace)
    base = tracing.reduce(trace)
    assert {k: red[k] for k in base} == base
    assert red["program_span_s"] == {} and red["scope_s"] == {
        program_trace.NO_SCOPE: pytest.approx(sum(base["op_s"].values()))}


NEW_METRICS = ("stage_ms.live", "stage_us_per_su.live", "dispatch_ms.live",
               "spool_read_ms.live", "spool_decode_ms.live",
               "stats_push_ms.live", "stats_upload_ms.live",
               "ingest_dev_ms.live", "store_emit_ms.live")


def test_readers_on_a_recorded_trace_with_program_spans():
    """Two supersteps of a traced ``iot1k.live`` run on a v5e chip: every
    new metric reads a number, the program's spans fit inside the
    harness's, and the round's device time falls under named scopes."""
    with open(os.path.join(DATA, "trace_v5e_iot1k_live_program.json")) as f:
        trace = json.load(f)
    red = program_trace.reduce(trace)
    host = _host(trace)
    harness = {}
    for ev in host:
        if ev[0].startswith("bench.") and ev[0] != tracing.SEGMENT:
            name = ev[0][len("bench."):]
            harness[name] = harness.get(name, 0.0) + ev[2] * 1e-9
    seg = {"spans": harness, "supersteps": 2, "rounds": 8, "window_reads": 1}
    rec = record(trace=red, seg=seg)
    got = {m: read(m, rec) for m in NEW_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    per = {k: v / 2 * 1e3 for k, v in harness.items()}   # ms a superstep
    assert got["stage_ms.live"] + got["dispatch_ms.live"] <= per["superstep"]
    assert got["spool_read_ms.live"] + got["spool_decode_ms.live"] <= \
        per["readback"]
    assert got["stats_upload_ms.live"] <= got["stats_push_ms.live"] <= \
        per["stats_fold"]
    assert red["program_span_calls"]["repro.stage"] == 2
    assert red["program_span_calls"]["repro.stats.push"] == 8
    assert got["stage_us_per_su.live"] == pytest.approx(
        red["program_span_s"]["repro.stage"]
        / red["program_span_args"]["repro.stage"]["sus"] * 1e6)
    # the fused round's kernel under round_fuse; the round's other ops
    # under a named scope
    ops = [ev for p in trace["planes"] if tracing.is_device_plane(p["name"])
           for ln in p["lines"] for ev in ln["events"]
           if ev[0].startswith("jit_superstep/")]
    kernel = [ev for ev in ops if "tpu_custom_call" in ev[0]]
    assert kernel and all(program_trace.innermost_scope(ev[3]) ==
                          "round_fuse" for ev in kernel)
    assert red["scope_s"]["round_fuse"] > red["scope_s"]["store_emit"] > 0


# --------------------------------------------------------------------------
# sink_overflow: winners beyond a round's sink buffer
# --------------------------------------------------------------------------

def _fanout_engine(sink_buffer, spool_slots=0, n_shards=1, subs=6):
    """One source with ``subs`` subscribers: a round emits ``subs``
    winners at once (on shard 1 when sharded)."""
    cfg = EngineConfig(n_streams=16, batch=8, queue=64, max_in=1, max_out=6,
                       sink_buffer=sink_buffer, sink_spool_slots=spool_slots,
                       n_shards=n_shards)
    reg = Registry(cfg)
    t = reg.create_tenant("t")
    a = reg.create_stream(t, "a", ["v"])
    for i in range(7 if n_shards > 1 else 0):   # fill shard 0
        reg.create_stream(t, f"p{i}", ["v"])
    for i in range(subs):
        reg.create_composite(t, f"c{i}", ["v"], [a], {"v": "a.v + 1"})
    eng = create_engine(reg)
    eng.post(a, [1.0], ts=1)
    return eng


@pytest.mark.parametrize("n_shards,sink_buffer,spool_slots,overflow,spool", [
    (1, 2, 0, 4, 0), (1, 4, 3, 2, 1), (1, 8, 0, 0, 0), (2, 2, 0, 4, 0)],
    ids=["sink", "sink+spool", "fits", "sharded"])
def test_sink_overflow_counts_what_the_spool_never_sees(
        n_shards, sink_buffer, spool_slots, overflow, spool):
    if len(jax.devices()) < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    eng = _fanout_engine(sink_buffer, spool_slots, n_shards)
    sp = eng.superstep(2)                 # round 0 ingests, round 1 emits x6
    records = sum(int(np.asarray(b.valid).sum())
                  for b in eng.spool_sinks(sp))
    c = eng.counters()
    assert c["emitted"] == 6
    assert c["sink_overflow"] == overflow
    assert c["dropped_spool"] == spool
    assert c["emitted"] - records == c["sink_overflow"] + c["dropped_spool"]


def test_sink_overflow_on_the_per_round_path_and_across_a_snapshot():
    eng = _fanout_engine(sink_buffer=2)
    sinks = [eng.round(), eng.round()]
    assert sum(int(np.asarray(b.valid).sum()) for b in sinks) == 2
    assert eng.counters()["sink_overflow"] == 4
    arrays, meta = eng.snapshot()
    assert int(arrays["state/stats/sink_overflow"]) == 4
    old = {k: v for k, v in arrays.items() if k != "state/stats/sink_overflow"}
    twin = _fanout_engine(sink_buffer=2)
    twin._install_snapshot(old, meta)      # a snapshot that predates the key
    assert twin.counters()["sink_overflow"] == 0
    for arr, n in ((arrays, 4), (old, 0)):  # resize carries it like the rest
        moved, _ = reshard_snapshot(arr, meta, 2)
        assert int(np.sum(moved["state/stats/sink_overflow"])) == n
