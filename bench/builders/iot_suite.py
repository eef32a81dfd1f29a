"""Builder kind ``iot_suite``: the program's own IoT suite of ETL and STATS
tenants (RIoTBench's kinds of dataflow, not its task graphs), built
through ``repro.workloads.build_suite`` (sharded over the cell's chips
when ``n_shards`` > 1), and the plain semantics of each stage.

Per tenant, in tenant order, rows are allocated in creation order:

* ETL (5 rows): raw -> parse (``v*0.5``) -> filter (passes
  ``-15 < v < 35``) -> interp (``(v + prev)*0.5``) -> annot (``v``, and
  ``alert = v > 25``); annot is the terminal sink;
* STATS (2 rows): raw -> clean (passes ``-40 < v < 80``,
  ``(v + prev)*0.5``); clean is the terminal sink, and its emissions feed
  the host window plane, whose last-``window`` aggregates are read every
  ``window`` rounds.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.builders import Deployment, check_cfg
from bench.reference import Topology

ROWS = {"etl": 5, "stats": 2}
CFG_KEYS = ("n_streams", "batch", "queue", "superstep", "max_in", "max_out",
            "prog_len", "n_temps", "n_consts", "channels", "sink_buffer",
            "n_shards", "partition", "exchange_slots", "fused_round")


# ---- the stages' semantics: (inputs, prev, rounding) -> (values, passes)
def parse(ins, prev, q):
    return (q(ins[0][0] * 0.5),), True


def range_filter(ins, prev, q):
    v = ins[0][0]
    return (v,), -15.0 < v < 35.0


def interp(ins, prev, q):
    return (q(q(ins[0][0] + prev[0]) * 0.5),), True


def annotate(ins, prev, q):
    v = ins[0][0]
    return (v, 1.0 if v > 25.0 else 0.0), True


def stats_clean(ins, prev, q):
    v = ins[0][0]
    return (q(q(v + prev[0]) * 0.5),), -40.0 < v < 80.0


STAGES = {"etl": (parse, range_filter, interp, annotate),
          "stats": (stats_clean,)}


def _kinds(params: dict) -> List[str]:
    kinds = params["kinds"]
    return [kinds[t % len(kinds)] for t in range(params["n_tenants"])]


def layout(params: dict):
    """Per tenant: its rows in creation order (source first)."""
    rows, sid = [], 0
    for kind in _kinds(params):
        rows.append(list(range(sid, sid + ROWS[kind])))
        sid += ROWS[kind]
    return rows


def topology(params: dict) -> Topology:
    N = params["n_streams"]
    inputs: List[List[int]] = [[] for _ in range(N)]
    transforms = [None] * N
    for kind, rows in zip(_kinds(params), layout(params)):
        for prev_row, row, fn in zip(rows, rows[1:], STAGES[kind]):
            inputs[row] = [prev_row]
            transforms[row] = fn
    return Topology(n_streams=N, n_shards=params["n_shards"],
                    batch=params["batch"], queue=params["queue"],
                    channels=params["channels"], inputs=inputs,
                    transforms=transforms)


def build(params: dict, chips: int) -> Deployment:
    from repro.workloads import build_suite

    if params["n_shards"] not in (1, chips):
        raise ValueError(f"n_shards {params['n_shards']} on {chips} chips")
    suite = build_suite(
        params["n_tenants"], kinds=tuple(params["kinds"]),
        n_shards=params["n_shards"], slo_rounds=None,
        window=params["window"], batch=params["batch"],
        queue=params["queue"], fused_round=params["fused_round"],
        cfg_overrides={"superstep": params["superstep"],
                       "partition": params["partition"],
                       "sink_buffer": params["sink_buffer"]})
    check_cfg(suite.cfg, params, CFG_KEYS)
    rows = layout(params)
    for flow, want in zip(suite.flows, rows):
        got = [flow.source.sid] + [s.sid for s in flow.stages]
        if got != want:
            raise ValueError(f"tenant rows {got}, expected {want}")
    kinds = _kinds(params)
    stats_sids = np.asarray([r[-1] for k, r in zip(kinds, rows)
                             if k == "stats"], np.int64)
    return Deployment(
        engine=suite.engine, K=params["superstep"],
        source_sids=np.asarray([r[0] for r in rows], np.int64),
        terminal_sids=np.asarray([r[-1] for r in rows], np.int64),
        stats=suite.stats if stats_sids.size else None,
        stats_sids=stats_sids, window_rounds=params["window"],
        path=getattr(suite.engine, "_path", ""))
