"""Builder kind ``table1_topology``: the paper's Table-I processing DAGs
(Section V-A), replicated over tenants, built through ``Registry`` and
``create_engine``.  Every composite sums its inputs (``in0.v + in1.v +
...``, left to right); sources are fed by the traffic, and composites
that nothing subscribes to are the terminal sinks.

The DAG generator is a copy of ``benchmarks/topologies.py`` (Table-I
parameters, geometric in-degree, preferential attachment), kept here so
the yardstick does not move with that script.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.builders import Deployment, check_cfg
from bench.reference import Topology


@dataclasses.dataclass(frozen=True)
class TopoSpec:
    name: str
    n_nodes: int
    n_sources: int
    mean_in: float          # mean operands per composite
    max_in: int
    seed: int = 0


PAPER_TABLE1 = {s.name: s for s in (
    TopoSpec("t1-small", 21, 11, 1.42, 9, seed=1),
    TopoSpec("t2-small", 19, 9, 1.94, 8, seed=2),
    TopoSpec("t3-medium", 42, 17, 3.54, 14, seed=3),
    TopoSpec("t4-medium", 43, 18, 3.51, 16, seed=4),
    TopoSpec("t5-big", 80, 30, 5.28, 29, seed=5),
    TopoSpec("t6-big", 74, 24, 6.18, 24, seed=6),
)}


def generate(spec: TopoSpec) -> List[List[int]]:
    """Per-node input lists, sources first, acyclic by construction."""
    rng = np.random.default_rng(spec.seed)
    n_comp = spec.n_nodes - spec.n_sources
    inputs: List[List[int]] = [[] for _ in range(spec.n_sources)]
    weights = np.ones(spec.n_nodes)
    for ci in range(n_comp):
        v = spec.n_sources + ci
        p = 1.0 / spec.mean_in
        k = int(np.clip(rng.geometric(p), 1, min(spec.max_in, v)))
        w = weights[:v] / weights[:v].sum()
        ins = rng.choice(v, size=k, replace=False, p=w)
        inputs.append(sorted(int(i) for i in ins))
        weights[list(ins)] += 1.0
        weights[v] = 1.0
    return inputs


def sum_inputs(ins, prev, q):
    acc = ins[0][0]
    for x in ins[1:]:
        acc = q(acc + x[0])
    return (acc,), True


def _graph(params: dict) -> List[List[int]]:
    """Inputs per row of the whole replicated deployment."""
    one = generate(PAPER_TABLE1[params["topology"]])
    n = len(one)
    return [[t * n + u for u in ins]
            for t in range(params["n_tenants"]) for ins in one]


def topology(params: dict) -> Topology:
    g = _graph(params)
    N = params["n_streams"]
    inputs = g + [[] for _ in range(N - len(g))]
    return Topology(n_streams=N, n_shards=params["n_shards"],
                    batch=params["batch"], queue=params["queue"],
                    channels=params["channels"], inputs=inputs,
                    transforms=[sum_inputs if ins else None
                                for ins in inputs])


def build(params: dict, chips: int) -> Deployment:
    from repro.core import EngineConfig, Registry
    from repro.core.engine import create_engine

    if params["n_shards"] not in (1, chips):
        raise ValueError(f"n_shards {params['n_shards']} on {chips} chips")
    keys = ("n_streams", "n_tenants", "batch", "queue", "superstep",
            "max_in", "max_out", "prog_len", "n_temps", "channels",
            "sink_buffer", "n_shards", "partition", "fused_round")
    cfg = EngineConfig(**{k: params[k] for k in keys})
    g = _graph(params)
    n = len(g) // params["n_tenants"]
    reg = Registry(cfg)
    nodes = []
    for t in range(params["n_tenants"]):
        tenant = reg.create_tenant(f"tenant{t}", quota_streams=10 ** 9)
        for v in range(n):
            ins = g[t * n + v]
            if not ins:
                nodes.append(reg.create_stream(tenant, f"t{t}.s{v}", ["v"]))
            else:
                expr = " + ".join(f"in{j}.v" for j in range(len(ins)))
                nodes.append(reg.create_composite(
                    tenant, f"t{t}.c{v}", ["v"], [nodes[u] for u in ins],
                    transform={"v": expr}))
    if [s.sid for s in nodes] != list(range(len(g))):
        raise ValueError("registry rows are not in creation order")
    engine = create_engine(reg)
    check_cfg(engine.cfg, params, keys)
    subscribed = {u for ins in g for u in ins}
    return Deployment(
        engine=engine, K=params["superstep"],
        source_sids=np.asarray([i for i, ins in enumerate(g) if not ins],
                               np.int64),
        terminal_sids=np.asarray([i for i, ins in enumerate(g)
                                  if ins and i not in subscribed], np.int64),
        path=getattr(engine, "_path", ""))
