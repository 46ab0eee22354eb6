"""Find the what-if cell's capacity: one open-loop window per offered rate.

    python3 bench/rate_sweep.py --config ycsb_c --traffic whatif \\
        --seed 7 --seconds 8 --rates 25 50 100 200 400

Runs on the chip, in one process: the cell's set-up once, then for each
rate a window of fresh questions (the seed plus the rate draws them).
For each rate it prints the due-to-answer p50, p95 and p99, how late
the generator ran, and the backlog trend: the p95 of the window's last
third over its first third (a queue that grows through the window reads
well above 1).  A what-if cell's fixed rate is set from this once, by
hand, before the cell is added; the mix need not be a cell's yet.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
# the script's own directory would shadow standard modules (trace)
sys.path[:] = [os.path.dirname(BENCH)] + [
    p for p in sys.path if os.path.abspath(p or ".") != BENCH]

from bench import drive, run, traffic as tr  # noqa: E402
from bench.cells import WhatIfCell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ycsb_c")
    ap.add_argument("--traffic", default="whatif")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    run.enable_compile_cache()
    import jax
    from bench.system import System
    if jax.devices()[0].platform != "tpu":
        print("the rate sweep measures a TPU; none found", file=sys.stderr)
        return 1
    config = tr.load_config(args.config)
    params = tr.load_traffic(args.traffic)
    system = System(config, 1)
    try:
        for rate in args.rates:
            cell_run = WhatIfCell(system, config,
                                  dict(params, rate_per_s=rate),
                                  args.seed + int(rate), args.seconds, 1)
            cell_run.setup()
            system.clear_memos()
            before = system.counters()
            cell_run.window()
            after = system.counters()
            slots = cell_run.slots
            lat = drive.latencies_ms(slots, cell_run.end)
            third = max(len(lat) // 3, 1)
            trend = drive.percentile(lat[-third:], 95) / max(
                drive.percentile(lat[:third], 95), 1e-9)
            late = drive.lateness_ms(slots)
            print(json.dumps({
                "rate_per_s": rate, "questions": len(slots),
                "answered": len(cell_run.answered),
                "p50_ms": drive.percentile(lat, 50),
                "p95_ms": drive.percentile(lat, 95),
                "p99_ms": drive.percentile(lat, 99),
                "backlog_trend": trend,
                "late_p99_ms": drive.percentile(late, 99),
                "max_batch": after["max_batch"],
                "batches": after["batches"] - before["batches"],
                "fused_traces": after["fused_traces"]
                - before["fused_traces"]}), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
