"""Find the highest open-loop rate a cell's engine sustains.

    python3 bench/sweep.py --workload iot1k.live --seconds 8 --rates 12000 16000
    python3 bench/sweep.py --workload iot1k.live --seconds 12 --pause 3 4 \
        --rates 3000 5000

Runs the cell's window once per offered rate, in one process (the
programs compile once), without the comparison, and prints one JSON line
per rate: events offered and failed, latency percentiles, supersteps, and
the backlog (host pending plus device queue) sampled through the window.
A rate is sustained when nothing is dropped and the backlog does not grow
over the window.  ``--pause AT S`` holds the host for S seconds once, AT
seconds into each window, as a stall of the machine's host would: the
rate found then is the one the engine sustains through such a stall.
The benchmark's own runs never sweep: the cell's rate is
fixed in its traffic file.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run                                        # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--pause", type=float, nargs=2, default=None,
                    metavar=("AT_S", "SECONDS"))
    args = ap.parse_args()
    from bench import harness, spec
    cell = spec.cell(args.workload)
    run.enable_compile_cache()
    devs, err = run.find_chips(cell.chips)
    if err:
        print(err, file=sys.stderr)
        return 2
    for rate in args.rates:
        out = harness.run_cell(cell, args.seed, args.seconds, False,
                              t_start=time.perf_counter(), compare=False,
                              traffic_params={"rate_per_s": rate},
                              pause=args.pause)
        rec = out.record
        pct = {q: float(np.percentile(rec.latency_ms, q))
               for q in (50, 95, 99)} if rec.latency_ms.size else {}
        print(json.dumps({
            "rate_per_s": rate, "pause": args.pause,
            "attempted": out.attempted,
            "failed": out.failed, "latency_ms": pct,
            "setup_s": rec.setup_s, "diagnostics": out.diagnostics,
            "backlog": [[round(t, 3), n] for t, n in out.backlog]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
