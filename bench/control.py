"""Readings of the control that ``correct`` must reject.

    python3 bench/control.py --workload iot1k.live --seconds 10 --seeds 1 2 3

The control is the plain reference computed in bfloat16, the precision
below the configuration's float32, put in the program's place: for each
seed (one process, so the programs compile once) the cell's window runs
on the chip as in a benchmark run, and then, over exactly the events that
window posted, the bfloat16 reference is compared with the float32
reference by the same numbers that decide ``correct``.  One JSON line per
seed gives both readings: ``program`` (the program against the float32
reference) and ``control``.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run                                        # noqa: E402


def control_checks(cell, out) -> dict:
    """The bfloat16 reference against the float32 reference, over the
    events ``out`` (a :class:`bench.harness.Outcome`) posted."""
    from bench import check, reference, spec
    params = cell.config["params"]
    topo = spec.kind("builders", cell.config["builder"]).topology(params)
    K = params["superstep"]
    ref = reference.simulate(topo, out.posted, K)
    ctl = reference.simulate(topo, out.posted, K, "bfloat16")
    aggs = [None, None]
    if out.agg_rounds:
        aggs = [reference.window_aggregates(r.records, out.stats_sids,
                                            params["window"], out.agg_rounds)
                for r in (ctl, ref)]
    return check.compare(ctl.records, ctl.values, ctl.timestamps,
                         ctl.dropped, ref, *aggs)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench import harness, spec
    cell = spec.cell(args.workload)
    run.enable_compile_cache()
    devs, err = run.find_chips(cell.chips)
    if err:
        print(err, file=sys.stderr)
        return 2
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                              t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": out.correct,
                          "program": out.checks,
                          "control": control_checks(cell, out)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
