"""Device-scaling measurements: sweep scoring at 1 vs N devices.

On an accelerator the process's own devices are the measurement: the
1-device side is the flat ``score`` call (or a one-shard service) and
the N-device side is the scoring-shard pool
(:class:`repro.serving.ScoringShardPool`) over every local device, both
in this process — it already holds the chips, so a child could not reach them.
On the CPU, JAX pins its device list at backend init, so the two device
counts run in *child* processes launched under
``--xla_force_host_platform_device_count=N`` (see
:mod:`repro.testing.devices`).  The children print one machine-readable
JSON line; the parent computes the scaling ratios:

* ``--child sweep``   — steady-state sweep-grid scoring (cells/sec) on a
  >= 4096-cell workload x design grid, the flat jit call vs the shard
  pool (parity asserted bit-for-bit before timing);
* ``--child serving`` — questions/sec through a
  ``DesignCalculatorService`` whose coalescing worker routes windows
  across the scoring-shard pool.

The acceptance bar (sharded >= 2x the single-device path at 4 devices)
needs 4 devices, and forced host devices are only physically meaningful
when they map onto >= 4 physical cores — XLA's host "devices" are
threads, so on a 1-core container they time-share the core and the ratio
measures scheduler overhead, not scaling.  ``_apply_bar`` therefore
asserts the bar only where both hold and otherwise records an explicit
waiver string in the emitted row, so the measured numbers still land in
the BENCH trajectory without pretending the bar was met or moving it.

``run(smoke=True)`` is the in-process pool-parity pass wired into
``benchmarks/run.py --smoke``: no subprocesses, no timing bars.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from benchmarks.common import _print_table

#: sharded-vs-single-device throughput bar, asserted at >= BAR_DEVICES
SCALING_TARGET = 2.0
#: the forced device count the bar is measured at
BAR_DEVICES = 4
#: physical cores needed for BAR_DEVICES forced devices to scale at all
BAR_MIN_CORES = 4

_JSON_PREFIX = "DEVICE_SCALING_JSON "


def _steady_state(fn: Callable, reps: int = 7) -> float:
    """Median wall-clock of ``fn`` after a warm call (compiles excluded)."""
    fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _sweep_inputs(n_designs: int, n_points: int):
    from repro.core.autocomplete import (default_candidates,
                                         default_terminals,
                                         enumerate_completions)
    from repro.core.synthesis import Workload
    frontier = list(enumerate_completions((), default_candidates(),
                                          default_terminals(), 2,
                                          "device-scaling"))
    while len(frontier) < n_designs:       # tile up to the design floor
        frontier = frontier + frontier
    frontier = frontier[:n_designs]
    base = Workload(n_entries=100_000, n_queries=100)
    workloads = [dataclasses.replace(base, zipf_alpha=0.25 * i)
                 for i in range(n_points)]
    mixes = [{"get": 60.0 + i, "range_get": 20.0, "update": 20.0 - i}
             for i in range(n_points)]
    return frontier, workloads, mixes


# ---------------------------------------------------------------------------
# children: one measurement per forced device count
# ---------------------------------------------------------------------------
def _child_sweep(quick: bool) -> Dict:
    import numpy as np

    import jax
    from repro.core import batchcost
    from repro.core.hardware import hw3
    from repro.serving import ScoringShardPool

    hw = hw3()
    n_designs, n_points = (512, 8) if quick else (1024, 8)
    frontier, workloads, mixes = _sweep_inputs(n_designs, n_points)
    sweep = batchcost.pack_sweep(frontier, workloads, mixes)
    cells = n_designs * n_points

    flat = sweep.score(hw)
    pool = ScoringShardPool(min_cells_per_shard=1)
    try:
        pooled, _ = pool.score_sweep(sweep, hw)
        assert np.array_equal(pooled, flat), \
            "the shard pool's sweep diverged from the flat jit path"
        flat_s = _steady_state(lambda: sweep.score(hw))
        sharded_s = _steady_state(lambda: pool.score_sweep(sweep, hw))
    finally:
        pool.close()
    return {
        "devices": jax.device_count(),
        "platform": jax.default_backend(),
        "cells": cells,
        "flat_cells_per_s": cells / max(flat_s, 1e-12),
        "sharded_cells_per_s": cells / max(sharded_s, 1e-12),
    }


def _child_serving(quick: bool, shards: Optional[int] = None) -> Dict:
    """Questions/sec through a service routing over ``shards`` scoring
    shards (default: every local device)."""
    import jax
    from repro.core.hardware import hw1
    from repro.serving import DesignCalculatorService

    hw = hw1()
    n_designs, n_points = (128, 8) if quick else (256, 8)
    n_questions = 8
    frontier, workloads, mixes = _sweep_inputs(n_designs, n_points)
    # every question sweeps a slightly different workload continuum so
    # repeat submissions measure scoring throughput, not answer reuse
    variants = [[dataclasses.replace(w, n_queries=100 + q)
                 for w in workloads] for q in range(n_questions)]
    service = DesignCalculatorService(
        [hw], scoring_shards=shards or jax.device_count(),
        shard_min_cells=max((n_designs * n_points) // 8, 1),
        window_s=0.005)
    try:
        service.submit_sweep(frontier, variants[0], hw,
                             mixes).result(timeout=300)   # warm + compile
        t0 = time.perf_counter()
        futures = [service.submit_sweep(frontier, v, hw, mixes)
                   for v in variants]
        for fut in futures:
            fut.result(timeout=300)
        wall = time.perf_counter() - t0
        stats = service.stats()
    finally:
        service.stop()
    return {
        "devices": shards or jax.device_count(),
        "platform": jax.default_backend(),
        "questions": n_questions,
        "questions_per_s": n_questions / max(wall, 1e-12),
        "shard_dispatches": stats["shard_dispatches"],
    }


_CHILDREN = {"sweep": _child_sweep, "serving": _child_serving}


def _run_child(mode: str, n_devices: int, quick: bool) -> Dict:
    from repro.testing.devices import run_under_devices
    argv = ["-m", "benchmarks.device_scaling", "--child", mode]
    if quick:
        argv.append("--quick")
    proc = run_under_devices(n_devices, argv)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device-scaling child {mode!r} failed under {n_devices} "
            f"devices:\n{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(_JSON_PREFIX):
            return json.loads(line[len(_JSON_PREFIX):])
    raise RuntimeError(f"device-scaling child {mode!r} printed no "
                       f"measurement line:\n{proc.stdout[-2000:]}")


def _measure(mode: str, quick: bool) -> Tuple[Dict, Dict]:
    """The (1-device, N-device) measurements of one child mode: in this
    process on an accelerator, in forced-host-device children on the
    CPU."""
    import jax
    if jax.default_backend() == "cpu":
        return (_run_child(mode, 1, quick),
                _run_child(mode, BAR_DEVICES, quick))
    if mode == "sweep":
        both = _child_sweep(quick)    # flat vs the pool, one process
        return both, both
    return _child_serving(quick, shards=1), _child_serving(quick)


def _apply_bar(row: Dict, speedup_key: str, multi: Dict) -> Dict:
    """Assert the >= 2x bar, or record a waiver where the N-device side
    has fewer than 4 devices or its 4 forced host devices cannot occupy
    4 physical cores."""
    cores = os.cpu_count() or 1
    row["devices"] = multi["devices"]
    row["platform"] = multi["platform"]
    if multi["devices"] < BAR_DEVICES:
        row["scaling_bar"] = (
            f"waived: {multi['devices']} {multi['platform']} device(s) < "
            f"{BAR_DEVICES} (measured ratio recorded unchanged)")
    elif multi["platform"] != "cpu" or cores >= BAR_MIN_CORES:
        row["scaling_bar"] = f"asserted >= {SCALING_TARGET:.0f}x"
        assert row[speedup_key] >= SCALING_TARGET, \
            (f"{speedup_key} = {row[speedup_key]:.2f}x is below the "
             f"{SCALING_TARGET:.0f}x device-scaling bar at "
             f"{BAR_DEVICES} devices on {cores} cores")
    else:
        row["scaling_bar"] = (
            f"waived: {cores} physical core(s) < {BAR_MIN_CORES}; "
            f"{BAR_DEVICES} forced host devices time-share the core(s), "
            f"so the >= {SCALING_TARGET:.0f}x bar is unattainable here "
            f"(measured ratio recorded unchanged)")
    return row


# ---------------------------------------------------------------------------
# parent rows, consumed by search_bench / load_bench trajectories
# ---------------------------------------------------------------------------
def sweep_scaling_row(quick: bool = False) -> Dict:
    """Sweep-grid cells/sec at 1 vs N devices — the BENCH_search
    device-scaling row."""
    base, multi = _measure("sweep", quick)
    speedup = multi["sharded_cells_per_s"] / max(
        base["flat_cells_per_s"], 1e-12)
    return _apply_bar({
        "search": "device_scaling",
        "designs": base["cells"] // 8,
        "workloads": 8,
        "cells": base["cells"],
        "sweep_cells_per_s": base["flat_cells_per_s"],
        "sharded_cells_per_s_4dev": multi["sharded_cells_per_s"],
        "speedup_sharded_4dev_vs_1dev": speedup,
    }, "speedup_sharded_4dev_vs_1dev", multi)


def serving_scaling_row(quick: bool = False) -> Dict:
    """Service questions/sec at 1 vs N scoring shards — the BENCH_load
    device-scaling fields."""
    base, multi = _measure("serving", quick)
    speedup = multi["questions_per_s"] / max(base["questions_per_s"],
                                             1e-12)
    return _apply_bar({
        "questions_per_s_1dev": base["questions_per_s"],
        "questions_per_s_4dev": multi["questions_per_s"],
        "shard_dispatches_4dev": multi["shard_dispatches"],
        "speedup_serving_4dev_vs_1dev": speedup,
    }, "speedup_serving_4dev_vs_1dev", multi)


def _smoke() -> None:
    """In-process pool-parity pass (the ``run.py --smoke`` hook): the
    shard pool's merged sweep and frontier must be bit-identical to the
    flat jit call at whatever device count this process has."""
    import numpy as np

    import jax
    from repro.core import batchcost
    from repro.core.hardware import hw3
    from repro.serving import ScoringShardPool

    hw = hw3()
    frontier, workloads, mixes = _sweep_inputs(64, 4)
    sweep = batchcost.pack_sweep(frontier, workloads, mixes)
    flat = sweep.score(hw)
    packed = sweep.frontiers[0]
    pool = ScoringShardPool(min_cells_per_shard=1)
    try:
        pooled, used = pool.score_sweep(sweep, hw)
        assert used >= 1 and np.array_equal(pooled, flat), \
            "shard-pool merge diverged from the flat grid"
        totals, _ = pool.score_frontier(packed, hw)
        assert np.array_equal(totals, packed.score(hw)), \
            "shard-pool frontier merge diverged from the flat call"
    finally:
        pool.close()
    print(f"device-scaling smoke: pool parity ok "
          f"({jax.device_count()} device(s), {used} pool shard(s))")


def run(quick: bool = False, smoke: bool = False) -> None:
    if smoke:
        _smoke()
        return
    rows: List[Dict] = [sweep_scaling_row(quick)]
    serving = serving_scaling_row(quick)
    rows.append({"search": "device_scaling_serving", **serving})
    _print_table("device_scaling [standalone — trajectory rows are "
                 "appended by search_bench/load_bench]", rows)


def main(argv: Sequence[str] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", choices=sorted(_CHILDREN))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        print(_JSON_PREFIX + json.dumps(_CHILDREN[args.child](args.quick)))
        return
    run(quick=args.quick, smoke=args.smoke)


if __name__ == "__main__":
    main()
