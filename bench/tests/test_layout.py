"""``BENCHMARK.json`` keeps the benchmark's format, every name it gives
has its file, and a new configuration, traffic mix or metric is found by
name with no edit to a file already there."""
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_format():
    path = os.path.join(spec.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    bm = spec.benchmark()
    assert set(bm) == TOP
    assert bm["paths"] == ["bench"] and 1 <= bm["run_seconds"] <= 51
    assert all(text_ok(w) for w in bm["command"])
    names = set()
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and text_ok(c["source"])
        assert text_ok(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        names.add(c["name"])
    cells = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert text_ok(w["why"])
        assert os.path.isfile(os.path.join(spec.BENCH, "traffic",
                                           w["traffic"] + ".json"))
        cells.add(w["name"])
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= \
        max(1, len(bm["workloads"]) // 2)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert text_ok(m["layer"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= set(
            e2e[m["moves"]].get("workloads", cells))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        f = spec.metric_file(m["name"])
        spec.kind("readers", f["reader"])
    for cell in cells:                       # each cell reports enough
        c = spec.cell(cell)
        assert "setup_s" in [m["name"] for m in c.end_to_end]
        assert len(c.end_to_end) >= 2 and c.per_layer


def test_configuration_files_hold_their_sizes():
    for c in spec.benchmark()["configs"]:
        f = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert {"builder", "source", "precision", "guarantees", "params",
                "reduced", "assumed"} <= set(f)
        assert f["reduced"] == c["reduced"]
        builder = spec.kind("builders", f["builder"])
        topo = builder.topology(f["params"])
        assert topo.n_streams == f["params"]["n_streams"]


NEW_CELL = textwrap.dedent("""
    import json, sys
    from bench import spec, run
    from bench.harness import RunRecord
    import numpy as np
    c = spec.cell("new.cell")
    assert c.config["params"]["n_tenants"] == 2048
    assert c.traffic["params"]["rate_per_s"] == 777.0
    spec.kind("traffic", c.traffic["generator"]).make(
        c.traffic["params"], 4, 1.0, 1)
    rec = RunRecord(1.0, 1.0, np.zeros(0), 0, np.zeros(0),
                    c.config["params"], "TPU v5 lite")
    print(json.dumps(run.read_metrics(c, rec, True)))
""")


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "iot_etl_stats_1k.json").read_text())
    cfg["params"].update(n_tenants=2048, n_streams=7170)
    (b / "configs" / "new_cfg.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "live.json").read_text())
    mix["params"]["rate_per_s"] = 777.0
    (b / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (b / "readers" / "constant.py").write_text(
        "def read(rec, args):\n    return args['value']\n")
    (b / "metrics" / "new_metric.json").write_text(json.dumps(
        {"reader": "constant", "args": {"value": 42.0}}))
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "new_cfg", "source": "x",
                          "file": "bench/configs/new_cfg.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "new.cell", "config": "new_cfg",
                            "traffic": "new_mix", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "new_metric", "unit": "ms",
                            "better": "lower", "source": "host_clock",
                            "layer": "x", "moves": "latency_p50_ms",
                            "workloads": ["new.cell"]})
    for m in bm["end_to_end"]:
        if "workloads" in m and "iot1k.live" in m["workloads"]:
            m["workloads"].append("new.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(tmp_path), os.path.join(spec.ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", NEW_CELL], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    metrics = json.loads(out.stdout.strip().splitlines()[-1])
    assert metrics == {"new_metric": {"value": 42.0, "unit": "ms"}}


def test_run_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         "iot1k.live", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
