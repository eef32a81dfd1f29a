"""Benchmark driver — one section per paper table/figure.

  experiment1  -> Table I + end-to-end SU latency (paper §V-B, Fig. 4)
  experiment2  -> length / in-degree / out-degree sweeps (paper Fig. 6/7)
  blocking     -> lock-free vs blocking-join ablation (paper §IV-C claim)
  windows      -> sliding-window aggregator throughput (paper §VII, ours)
  roofline     -> renders the dry-run roofline table (needs dryrun JSONs)

``python -m benchmarks.run [--quick] [--sections a,b,c]``
"""
from __future__ import annotations

import argparse
import time


def _sec(title):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}", flush=True)


def bench_windows(quick: bool):
    import jax.numpy as jnp
    from repro.core.windows import aggregate, init_window_store, push

    n, w, c = (4096, 64, 4) if quick else (65536, 64, 4)
    st = init_window_store(n, w, c)
    sid = jnp.arange(min(n, 1024), dtype=jnp.int32)
    vals = jnp.ones((sid.shape[0], c), jnp.float32)
    mask = jnp.ones((sid.shape[0],), bool)
    # CPU timing uses the jnp path; the Pallas kernel is the TPU path
    # (validated in tests/test_kernels.py via interpret mode).
    st = push(st, sid, vals, jnp.ones_like(sid), mask)   # compile
    _ = aggregate(st, use_kernel=False)["mean"].block_until_ready()
    t0 = time.perf_counter()
    reps = 20
    for i in range(reps):
        st = push(st, sid, vals * i, jnp.full_like(sid, i + 2), mask)
        _ = aggregate(st, use_kernel=False)["mean"].block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    rate = sid.shape[0] / dt
    print(f"streams={n} window={w} channels={c}")
    print(f"push+aggregate: {dt*1e3:.2f} ms/round, {rate/1e6:.2f}M SU/s")


def main():
    from repro.launch.compiles import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps (CI-sized)")
    ap.add_argument("--sections", default="experiment1,experiment2,blocking,"
                    "windows,roofline")
    args = ap.parse_args()
    sections = set(args.sections.split(","))

    if "experiment1" in sections:
        _sec("Experiment 1 — pseudo-random topologies (paper Table I / §V-B)")
        from benchmarks import experiment1
        experiment1.main(n_updates=3 if args.quick else 10)

    if "experiment2" in sections:
        _sec("Experiment 2 — length / in-degree / out-degree (paper Fig. 7)")
        from benchmarks import experiment2
        if args.quick:
            experiment2.main(lengths=(1, 5, 10, 25), degrees=(1, 5, 10, 25))
        else:
            experiment2.main()

    if "blocking" in sections:
        _sec("Ablation — lock-free vs blocking-join (paper §IV-C)")
        from benchmarks import baseline_blocking
        baseline_blocking.main(n_ticks=20 if args.quick else 50)

    if "windows" in sections:
        _sec("Sliding-window aggregators (paper §VII future work)")
        bench_windows(args.quick)

    if "roofline" in sections:
        _sec("Roofline (from dry-run artifacts)")
        from benchmarks import roofline
        try:
            roofline.main()
        except Exception as e:                     # dryrun not yet produced
            print(f"(roofline table unavailable: {e})")


if __name__ == "__main__":
    main()
