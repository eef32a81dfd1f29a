"""Run one benchmark cell once on the chips of the host it runs on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name
through ``BENCHMARK.json``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics), the
``device``, with ``--trace 1`` a ``breakdown``, and last the numbers
compared with their limits, which also close standard error.  With no
TPU, or fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CACHE = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside this
    checkout whatever the environment says (the path is part of the
    key), caching every program so warm set-up loads them all."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CACHE


def find_chips(chips: int):
    """The TPU devices, or an error message: never the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        return None, f"no accelerator: {e}"
    if devs[0].platform != "tpu":
        return None, f"no TPU: JAX sees {devs[0].platform} devices"
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX sees {len(devs)}"
    return devs, None


def result_line(out, metrics, breakdown=None) -> dict:
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": out.device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["diagnostics"] = out.diagnostics
    line["checks"] = out.checks          # the numbers compared, last
    return line


def read_metrics(cell, rec, trace: bool) -> dict:
    from bench import spec
    metrics = {}
    for m in cell.metrics(trace):
        f = spec.metric_file(m["name"])
        value = spec.kind("readers", f["reader"]).read(rec, f.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler trace under .bench_trace/")
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.cell(args.workload)
    enable_compile_cache()
    devs, err = find_chips(cell.chips)
    if err:
        print(err, file=sys.stderr)
        return 2

    from bench import harness, tracing
    trace_dir = os.path.join(TRACE_DIR, f"{cell.name}.{args.seed}")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, trace_dir=trace_dir,
                          keep_trace=args.keep_trace)
    metrics = read_metrics(cell, out.record, bool(args.trace))
    breakdown = None
    if args.trace and out.record.trace is not None:
        breakdown = tracing.breakdown(out.record.trace)
    print(json.dumps(out.diagnostics), file=sys.stderr)
    for name, c in out.checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result_line(out, metrics, breakdown)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
