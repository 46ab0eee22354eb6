"""Readings that set the limit of ``correct``, many seeds in one process.

    python3 bench/control.py --workload ycsb_a.sweep --seconds 4 \\
        --seeds 101 102 103 ...

For each seed: the cell's set-up and a short window at the cell's own
load, then two readings over the same sampled answers (bench/check.py):

* ``program`` -- the widest relative gap of the timed path's answers to
  the float64 reference (the lower reading: sound runs);
* ``control`` -- the same gap with the reference computed in bfloat16
  put in the program's place (the upper reading).

The limit in the configuration lies between the largest ``program`` and
the smallest ``control`` reading.  Runs on the chip; the benchmark's own
runs never run the control.
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

from bench import check, run, traffic as tr  # noqa: E402
from bench.cells import KINDS  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = {c["name"]: c for c in run.load_benchmark()["workloads"]}[
        args.workload]
    run.enable_compile_cache()
    import jax
    from bench.system import System
    if jax.devices()[0].platform != "tpu":
        print("the control readings are taken on a TPU; none found",
              file=sys.stderr)
        return 1
    config = tr.load_config(cell["config"])
    params = tr.load_traffic(cell["traffic"])
    chips = int(cell["chips"])
    system = System(config, chips)
    try:
        for seed in args.seeds:
            cell_run = KINDS[params["kind"]](system, config, params, seed,
                                             args.seconds, chips)
            cell_run.setup()
            system.clear_memos()
            cell_run.window()
            comparisons = cell_run.comparisons()
            print(json.dumps({
                "seed": seed, "compared": len(comparisons),
                "program": check.max_rel_err(comparisons,
                                             config["hardware"]),
                "control": check.max_rel_err(comparisons,
                                             config["hardware"],
                                             control=True)}), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
