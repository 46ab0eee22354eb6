"""Run one benchmark cell once, on the machine's TPU chips.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The run builds the hardware
profiles and a ``DesignCalculatorService``, warms every shape the
cell's traffic can produce (set-up), measures for ``--seconds`` seconds
(the window), then checks a sample of the window's answers against the
plain reference (:mod:`bench.check`).

With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it records a profiler trace of the window and reports the
cell's per-layer metrics (readers in ``bench/metrics/<metric>.py``).
The last line of stdout is one JSON object; the numbers compared for
``correct`` are the last lines of stderr.  A backend that is not a TPU,
or fewer chips than the cell asks for, exits 1 with no result.

JAX's persistent compilation cache lives at ``.bench_cache/jax`` in the
checkout, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")
# the script's own directory would shadow standard modules (trace)
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != BENCH]

from bench import check, trace as tracing, traffic as tr  # noqa: E402
from bench.cells import KINDS, log  # noqa: E402


def load_benchmark() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell_metrics(bench: Dict, cell: str, group: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, ctx: Dict) -> Optional[float]:
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (summed across threads)."""

    def __init__(self) -> None:
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration


def enable_compile_cache() -> str:
    path = os.path.join(CACHE, "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def execute(bench: Dict, cell: Dict, seed: int, seconds: float,
            trace_on: bool, devices: List, peaks: Optional[Dict],
            params: Optional[Dict] = None) -> Dict:
    """Set up, measure and check one run of ``cell`` on ``devices``;
    returns the result object (``checks`` last).  ``params`` replaces
    the traffic file's parameters (the tests run cells at small sizes)."""
    import jax
    from bench.system import System
    if trace_on and devices[0].platform != "tpu":
        raise RuntimeError("device metrics come only from a TPU trace")
    config = tr.load_config(cell["config"])
    params = params or tr.load_traffic(cell["traffic"])
    chips = int(cell["chips"])
    clock = CompileClock()
    system = System(config, chips)
    try:
        run = KINDS[params["kind"]](system, config, params, seed, seconds,
                                    chips)
        run.setup()
        system.clear_memos()
        setup_s = time.perf_counter() - T_START
        before = system.counters()
        compiled = clock.seconds
        log(f"set-up: setup_s={setup_s:.3f} compile_s={compiled:.3f} "
            f"fused_traces={before['fused_traces']}")
        trace_dir = os.path.join(CACHE, "trace")
        if trace_on:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracing.start(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                e2e = run.window()
        finally:
            if trace_on:
                jax.profiler.stop_trace()
        after = system.counters()
        delta = {k: after[k] - before[k] for k in after}
        log(f"window: compile_s={clock.seconds - compiled:.3f} "
            f"fused_traces={delta['fused_traces']} "
            f"batches={delta['batches']} answered={delta['answered']} "
            f"score_calls={delta['score_calls']} "
            f"shard_dispatches={delta['shard_dispatches']} "
            f"fallback_flat={delta['fallback_flat']} "
            f"fallback_grouped={delta['fallback_grouped']}")
        stats = [d.memory_stats() or {} for d in devices[:chips]]
        memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    finally:
        system.close()
    summary = None
    if trace_on:
        xplane = tracing.find_xplane(trace_dir)
        if xplane is None:
            raise tracing.TraceError(f"the profiler wrote no trace under "
                                     f"{trace_dir}")
        summary = tracing.require(
            tracing.summarize(tracing.load(xplane), chips), chips)
    checks = check.Checks()
    comparisons = run.comparisons()
    checks.at_least("answers_compared", len(comparisons), 1)
    checks.at_most("max_rel_err",
                   check.max_rel_err(comparisons, config["hardware"]),
                   float(config["correct"]["max_rel_err"]))
    checks.at_most("unanswered", run.unanswered(), 0)
    run.extra_checks(checks, delta)

    units = {}
    if trace_on:
        ctx = {"counters": delta, "trace": summary,
               "answered": len(run.answered), "peaks": peaks,
               "work_bytes": run.work_bytes()}
        values = {}
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            values[m["name"]] = read_metric(m["name"], ctx)
            units[m["name"]] = m["unit"]
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            units[m["name"]] = m["unit"]
    metrics = {name: {"value": values[name], "unit": unit}
               for name, unit in units.items()
               if values.get(name) is not None}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": checks.correct, "attempted": len(run.slots),
              "failed": sum(1 for s in run.slots if not s.ok),
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        log(f"trace: busy_by_device={summary['busy_by_device']} "
            f"fused_calls={summary['fused_calls']} "
            f"fused_s={summary['fused_s']}")
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks.items
    checks.report()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    enable_compile_cache()
    import jax
    from bench.peaks import peaks
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"no result: the cell needs {cell['chips']} TPU chip(s), "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    chip_peaks = peaks(devices[0].device_kind)
    result = execute(bench, cell, args.seed, args.seconds,
                     bool(args.trace), devices, chip_peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
