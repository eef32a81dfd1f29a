"""The ``table1_topology`` builder kind, which no cell uses yet: the
paper's Table-I DAGs replicated over tenants agree with the reference
under both generator kinds."""
import time

import numpy as np
import pytest

from bench import harness, spec
from bench.builders import table1_topology as t1


def params(topology="t1-small", n_tenants=3):
    one = t1.generate(t1.PAPER_TABLE1[topology])
    out_deg = np.bincount([u for ins in one for u in ins],
                          minlength=len(one))
    max_in = max(len(i) for i in one)
    n = len(one) * n_tenants
    return {"topology": topology, "n_tenants": n_tenants, "n_streams": n,
            "n_shards": 1, "partition": "block", "batch": 16, "queue": 512,
            "superstep": 4, "max_in": max_in, "max_out": int(out_deg.max()),
            "prog_len": max(16, 3 * max_in + 4),
            "n_temps": max(16, max_in + 4), "channels": 4,
            "sink_buffer": 16 * int(out_deg.max()), "fused_round": True}


def test_generator_matches_table1_shapes():
    for spec_ in t1.PAPER_TABLE1.values():
        g = t1.generate(spec_)
        assert len(g) == spec_.n_nodes
        assert sum(1 for ins in g if not ins) == spec_.n_sources
        assert all(max(ins, default=-1) < v for v, ins in enumerate(g))


@pytest.mark.parametrize("mix,rate", [("wave", None), ("live", 400.0)])
def test_table1_cell_is_correct(mix, rate):
    traffic = spec.load_json(f"{spec.BENCH}/traffic/{mix}.json")
    if rate:
        traffic["params"]["rate_per_s"] = rate
    cell = spec.Cell(name="table1.tiny", chips=1, config_name="table1",
                     config={"builder": "table1_topology",
                             "params": params()},
                     traffic_name=mix, traffic=traffic, end_to_end=[],
                     per_layer=[])
    out = harness.run_cell(cell, 2 ** 32 + 1, 1.5, False,
                          t_start=time.perf_counter())
    assert out.correct, out.checks
    assert out.diagnostics["records"] > 0
