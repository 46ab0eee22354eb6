"""Benchmark driver: one module per paper table/figure + the TPU roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
    PYTHONPATH=src python -m benchmarks.run --smoke

``--smoke`` is the fast validation path: it runs the repro-lint static
checks (``python -m tools.analyze``), then the search-engine,
population-search, workload-sweep, what-if-serving, sharded-scoring
and fault-injection parity checks at tiny sizes (every
engine against the scalar oracle, grouped sweep grids bit-identical to
per-workload loops, zero-recompile probes, one injected shard failure
and one NaN-bank corruption both healed to oracle parity), writes
**no** artifacts and
appends nothing to the BENCH_search / BENCH_serving trajectories —
CI-friendly, seconds not minutes.  The full trajectory run stays one
command (no flags).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (chaos_bench, design_space, device_scaling,
                        fig6_accuracy, fig7_bulkload_training,
                        fig8_cache_skew, fig9_design_search, hillclimb,
                        kernels_bench, load_bench, popsearch_bench,
                        roofline, search_bench, serving_bench)
from benchmarks.common import enable_compile_cache

BENCHES = [
    ("design_space", design_space.run),
    ("fig6_accuracy", fig6_accuracy.run),
    ("fig7_bulkload_training", fig7_bulkload_training.run),
    ("fig8_cache_skew", fig8_cache_skew.run),
    ("fig9_design_search", fig9_design_search.run),
    # perf trajectory: designs-costed-per-second, scalar vs grouped vs
    # fused (appends an entry to experiments/bench/BENCH_search.json)
    ("BENCH_search", search_bench.run),
    # search-quality trajectory: population search over the relaxed
    # continuum vs design_beam at an equal designs-costed cap
    # (appends to BENCH_search.json as well)
    ("BENCH_popsearch", popsearch_bench.run),
    # perf trajectory: questions/sec through the concurrent what-if
    # server, serial loop vs coalesced (BENCH_serving.json)
    ("BENCH_serving", serving_bench.run),
    # robustness trajectory: sustained mixed load through the hardened
    # server — priority-lane latency, shedding, warm restart
    # (BENCH_load.json)
    ("BENCH_load", load_bench.run),
    # robustness trajectory: the same mixed load under an ~5% seeded
    # fault plan — self-healing shard pool, degraded-engine chain,
    # worker resurrection, oracle parity under chaos (BENCH_chaos.json)
    ("BENCH_chaos", chaos_bench.run),
    ("hillclimb_design", hillclimb.run),
    ("kernels", kernels_bench.run),
    ("roofline", roofline.run),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast parity-only pass: tiny sizes, no artifacts,"
                         " no trajectory append")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        t0 = time.perf_counter()
        print("### repro-lint (smoke)", flush=True)
        from tools.analyze import render_text, run_paths
        findings = run_paths()
        if findings:
            print(render_text(findings), flush=True)
            sys.exit(1)
        print("### benchmark: BENCH_search (smoke)", flush=True)
        search_bench.run(smoke=True)
        print("### benchmark: BENCH_popsearch (smoke)", flush=True)
        popsearch_bench.run(smoke=True)
        print("### benchmark: BENCH_serving (smoke)", flush=True)
        serving_bench.run(smoke=True)
        print("### benchmark: BENCH_load (smoke)", flush=True)
        load_bench.run(smoke=True)
        print("### benchmark: BENCH_chaos (smoke)", flush=True)
        chaos_bench.run(smoke=True)
        print("### benchmark: device_scaling (smoke)", flush=True)
        device_scaling.run(smoke=True)
        print(f"### smoke done in {time.perf_counter() - t0:.1f}s")
        return
    if args.only and args.only not in {name for name, _ in BENCHES}:
        ap.error(f"unknown benchmark {args.only!r}; choose from "
                 f"{[name for name, _ in BENCHES]}")
    failures = []
    for name, fn in BENCHES:
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        print(f"### benchmark: {name}", flush=True)
        try:
            fn(quick=args.quick)
            print(f"### {name} done in {time.perf_counter() - t0:.1f}s\n",
                  flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"FAILED benchmarks: {failures}")
        sys.exit(1)
    print("all benchmarks passed")


if __name__ == "__main__":
    main()
