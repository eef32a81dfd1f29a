"""The rate, tail, span, idle-share and roofline arithmetic on synthetic
samples, and the trace reduction on a hand-made trace and on a 30 ms
slice recorded from an ``iot1k.live`` run on a TPU v5e chip."""
import json
import os

import numpy as np
import pytest

from bench import roofline, spec, tracing
from bench.harness import RunRecord

DATA = os.path.join(os.path.dirname(__file__), "data")
P1K = spec.load_json(os.path.join(spec.BENCH, "configs",
                                  "iot_etl_stats_1k.json"))["params"]


def reader(metric):
    f = spec.metric_file(metric)
    return lambda rec: spec.kind("readers", f["reader"]).read(
        rec, f.get("args", {}))


def record(**kw):
    base = dict(setup_s=12.5, seconds=10.0, latency_ms=np.zeros(0),
                delivered=0, late_ms=np.zeros(0), params=P1K,
                device_kind="TPU v5 lite")
    return RunRecord(**{**base, **kw})


def test_end_to_end_arithmetic():
    lat = np.arange(1, 1001, dtype=float)          # 1..1000 ms
    rec = record(latency_ms=lat, delivered=25000)
    assert reader("setup_s")(rec) == 12.5
    assert reader("latency_p50_ms")(rec) == pytest.approx(500.5)
    assert reader("latency_p90_ms")(rec) == pytest.approx(900.1)
    tail = spec.kind("readers", "latency").read(rec, {"q": 99})
    assert tail == pytest.approx(990.01)               # all samples' tail
    assert reader("delivered_per_s")(rec) == 2500.0
    assert reader("latency_p50_ms")(record()) is None   # nothing to read


def test_host_span_and_generator_arithmetic():
    seg = {"spans": {"post": 0.2, "superstep": 0.1, "readback": 0.05,
                     "stats_fold": 0.15}, "supersteps": 100, "rounds": 400,
           "window_reads": 50}
    rec = record(seg=seg, late_ms=np.linspace(0.0, 100.0, 101))
    assert reader("host_stage_ms.live")(rec) == pytest.approx(3.0)
    assert reader("readback_ms.wave")(rec) == pytest.approx(2.0)
    assert reader("gen_late_p99_ms")(rec) == pytest.approx(99.0)
    assert reader("host_stage_ms.live")(record()) is None


def hand_trace():
    """Two chips over a 400 ns segment; host spans on the host plane."""
    op = "jit_superstep/custom-call:tpu_custom_call/k"
    dev0 = [["jit_superstep/while/w", 0, 100], [op, 10, 30],
            ["jit_superstep/fusion/f", 50, 10],
            ["jit_window_agg_op/custom-call:tpu_custom_call/a", 200, 50]]
    dev1 = [[op, 0, 30], ["jit_superstep/fusion/f", 300, 100]]
    host = [["bench.segment", 0, 400], ["bench.post", 100, 100],
            ["bench.readback", 250, 150]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": dev0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops",
                                             "events": dev1}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


def test_reduction_on_a_hand_made_trace():
    red = tracing.reduce(hand_trace())
    assert red["n_chips"] == 2
    assert red["window_s"] == pytest.approx(400e-9)
    # chip 0 busy 100 + 50, chip 1 busy 30 + 100: mean 140
    assert red["busy_s"] == pytest.approx(140e-9)
    k = "jit_superstep/custom-call:tpu_custom_call/k"
    assert red["op_s"][k] == pytest.approx(30e-9)        # (30 + 30) / 2
    assert red["op_calls"][k] == 1.0
    assert "jit_superstep/while/w" not in red["op_s"]    # encloses ops
    # idle: chip 0 [100,200] post, [250,400] readback; chip 1 [30,100]
    # no span, [100,200] post, [200,250] none, [250,300] readback
    idle = red["idle_by_span_s"]
    assert idle["post"] == pytest.approx(100e-9)
    assert idle["readback"] == pytest.approx(100e-9)
    assert idle["(no span)"] == pytest.approx(60e-9)
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    seg = {"spans": {}, "supersteps": 2, "rounds": 8, "window_reads": 1}
    rec = record(trace=red, seg=seg)
    assert reader("idle_share.live")(rec) == pytest.approx(65.0)
    assert reader("device_ms.wave")(rec) == pytest.approx(70e-6)
    assert reader("round_kernel_ms.live")(rec) == pytest.approx(30e-6 / 8)
    assert reader("window_agg_ms")(rec) == pytest.approx(25e-6)
    assert reader("collective_ms")(rec) is None          # no such op
    assert tracing.breakdown(red)["device_ops"][0][0] == \
        "jit_superstep/fusion/f"


def test_reduction_on_a_recorded_chip_trace():
    with open(os.path.join(DATA, "trace_v5e_iot1k_live.json")) as f:
        red = tracing.reduce(json.load(f))
    assert red["n_chips"] == 1
    assert 0 < red["busy_s"] < red["window_s"] == pytest.approx(0.03)
    assert sum(red["idle_by_span_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    kernel = tracing.matching(red["op_calls"],
                              [["jit_superstep/", "tpu_custom_call"]])
    assert sum(kernel.values()) == 4                    # one superstep
    rec = record(trace=red, seg={"spans": {}, "supersteps": 1, "rounds": 4,
                                 "window_reads": 0})
    per_round = reader("round_kernel_ms.live")(rec)
    assert 0.5 < per_round < 5.0
    share = reader("round_kernel_roofline.live")(rec)
    least = roofline.least_time_s("fused_round", P1K, "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert share == pytest.approx(100 * least["seconds"] / per_round * 1e3)
    assert 0 < share < 100
    bd = tracing.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.least_time_s("fused_round", P1K, "TPU v9 imaginary")


def test_op_names_from_hlo_text():
    hlo = ('%closed_call.15 = (s32[1,256]{1,0:T(1,128)}, f32[2]) custom-call('
           's32[1,8192]{1,0:T(1,128)} %b), custom_call_target="tpu_custom_call"'
           ', operand_layout_constraints={}')
    assert tracing.op_name("jit_superstep(123)", hlo) == \
        "jit_superstep/custom-call:tpu_custom_call/closed_call.15"
    assert tracing.op_name("jit_superstep(1)", "%while.1 = (s32[]{:T(128)})"
                           " while((s32[]{:T(128)}) %t), condition=%c") == \
        "jit_superstep/while/while.1"
