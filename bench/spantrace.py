"""The program's own spans in a profiler trace, and a run that reports
them.

    python3 bench/spantrace.py --workload <cell> --seed <n> \\
        --seconds <s>

runs one cell once with ``--trace 1`` (as ``bench/run.py`` does), then
prints a ``spans:`` line to stderr: the device-idle seconds under each
span name (:func:`idle_by_span`), ``covered_pct`` (the share of
device-idle time that any ``repro.`` span covers, on any thread), each
program span's seconds by the trace (:func:`span_seconds`) and by the
program's own counter over the window (``span_counter_s``).  In the
window a sweep cell's client opens a ``bench.client.build`` span around
each sweep it builds, so client work shows apart from the program's.
The last line of stdout is ``bench/run.py``'s result object.  A program
without spans reports empty tables.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the script's own directory would shadow standard modules (trace)
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != BENCH]

from bench.trace import merge, window_of  # noqa: E402

#: prefix of the program's own spans (``repro.core.spans``)
PROGRAM_SPANS = "repro."
#: the benchmark's span around a sweep its client builds in the window
CLIENT_BUILD_SPAN = "bench.client.build"


def span_seconds(trace: Dict, window: Tuple[float, float]) -> Dict[str,
                                                                  float]:
    """Summed duration of each program span that starts in the window,
    over every host thread."""
    lo, hi = window
    out: Dict[str, float] = {}
    for events in trace["host"].values():
        for name, start, dur in events:
            if name.startswith(PROGRAM_SPANS) and lo <= start < hi:
                out[name] = out.get(name, 0.0) + dur * 1e-9
    return out


def _intersect_ns(a: Sequence[Tuple[float, float]],
                  b: Sequence[Tuple[float, float]]) -> float:
    """Length of the overlap of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(trace: Dict, window: Tuple[float, float],
                   chips: int) -> List[Tuple[float, float]]:
    """The stretches of the window in which none of the cell's ``chips``
    devices ran an operation."""
    lo, hi = window
    devices = sorted(trace["devices"],
                     key=lambda d: int(d.rsplit(":", 1)[1]))[:chips]
    busy = merge(((s, s + d) for dev in devices
                  for _, s, d in trace["devices"][dev]["ops"]), lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(trace: Dict, window: Tuple[float, float],
                 chips: int) -> Dict:
    """Device-idle seconds under each span name (the program's spans and
    the benchmark's client builds; a name's intervals count once where
    threads overlap), and ``covered_pct``: the share of device-idle time
    under any program span, or ``None`` when the device was never idle."""
    lo, hi = window
    idle = idle_intervals(trace, window, chips)
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    for events in trace["host"].values():
        for name, start, dur in events:
            if name.startswith(PROGRAM_SPANS) or name == CLIENT_BUILD_SPAN:
                by_name.setdefault(name, []).append((start, start + dur))
    seconds = {name: _intersect_ns(merge(spans, lo, hi), idle) * 1e-9
               for name, spans in sorted(by_name.items())}
    program = merge((iv for name, spans in by_name.items()
                     if name.startswith(PROGRAM_SPANS) for iv in spans),
                    lo, hi)
    idle_ns = sum(b - a for a, b in idle)
    covered = (100.0 * _intersect_ns(program, idle) / idle_ns
               if idle_ns > 0 else None)
    return {"seconds": seconds, "idle_s": idle_ns * 1e-9,
            "covered_pct": covered}


def span_line(events: Dict, chips: int, before: Dict[str, float],
              after: Dict[str, float]) -> str:
    """The ``spans:`` line: ``before`` and ``after`` are the program's
    ``span_s.<name>`` counters at the window's open and close."""
    window = window_of(events)
    idle = idle_by_span(events, window, chips)
    counted = {k[len("span_s."):]: after[k] - before[k]
               for k in after if k.startswith("span_s.") and k in before}
    return (f"spans: covered_pct={idle['covered_pct']} "
            f"idle_s={idle['idle_s']} "
            f"idle_by_span={json.dumps(idle['seconds'])} "
            f"span_seconds={json.dumps(span_seconds(events, window))} "
            f"span_counter_s={json.dumps(counted)}")


def _program_counters() -> Dict[str, float]:
    try:
        from repro.core import spans
    except ImportError:
        return {}
    return spans.counters()


def _client_build(build):
    """Open a ``bench.client.build`` span around a client's build once
    the window is open."""
    @functools.wraps(build)
    def wrapped(self, *args):
        if not getattr(self, "in_window", False):
            return build(self, *args)
        import jax
        with jax.profiler.TraceAnnotation(CLIENT_BUILD_SPAN):
            return build(self, *args)
    return wrapped


def _snapshot_window(window, snapshots: Dict[str, Dict]):
    """Snapshot the program's span counters as the window opens and
    closes, inside the trace."""
    @functools.wraps(window)
    def wrapped(self):
        snapshots["before"] = _program_counters()
        self.in_window = True
        try:
            return window(self)
        finally:
            snapshots["after"] = _program_counters()
    return wrapped


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import cells, run, trace as tracing
    bench = run.load_benchmark()
    by_name = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in by_name:
        print(f"unknown workload {args.workload!r}; cells: "
              f"{sorted(by_name)}", file=sys.stderr)
        return 2
    cell = by_name[args.workload]
    run.enable_compile_cache()
    import jax
    from bench.peaks import peaks
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"no result: the cell needs {cell['chips']} TPU chip(s), "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    snapshots: Dict[str, Dict] = {}
    for kind in set(cells.KINDS.values()):
        kind.window = _snapshot_window(kind.window, snapshots)
    cells.SweepCell._build = _client_build(cells.SweepCell._build)
    result = run.execute(bench, cell, args.seed, args.seconds, True,
                         devices, peaks(devices[0].device_kind))
    xplane = tracing.find_xplane(os.path.join(run.CACHE, "trace"))
    cells.log(span_line(tracing.load(xplane), int(cell["chips"]),
                        snapshots.get("before", {}),
                        snapshots.get("after", {})))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
