"""Reduction of a profiler trace to device metrics.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists (``[name, start_ns, duration_ns]``), which is also the
form of the small recorded trace the tests keep.  The reductions are
pure functions of those lists:

* device busy time is the *union* of the intervals in which an
  operation ran on a device, clipped to the window -- overlapping
  operations count once; the idle share is 1 minus busy over the window,
  averaged over the devices the cell uses;
* the fused scoring programs are found by their XLA module names
  (:data:`FUSED_MODULES`), and their device time is the sum of those
  modules' durations;
* the longest device-idle gaps are labelled with the host event that
  overlaps each one most (the window's own span excepted).

The window is the span the benchmark itself records around its measured
window (:data:`WINDOW_SPAN`), read from the same trace, so host and
device times share one clock.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the benchmark's own span around the measured window
WINDOW_SPAN = "bench.window"
#: substrings of the XLA module names of the fused scoring programs
#: (``jit(_score_kernel)``, ``jit(_sweep_kernel)`` and their pmap forms)
FUSED_MODULES = ("_score_kernel", "_sweep_kernel")
#: device trace lines that hold operations and whole programs
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


def start(trace_dir: str) -> None:
    """Start the profiler with JAX's spans and the device's operations but
    without its Python tracer, which records every Python call: it slowed
    a sweep window on the host 2.3x and wrote 200 MB for 6 s of it."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _is_device(plane_name: str) -> bool:
    prefix = "/device:TPU:"
    return plane_name.startswith(prefix) and \
        plane_name[len(prefix):].isdigit()


def load(xplane_path: str) -> Dict:
    """Events of a profiler trace: per device its operations and modules,
    and the host threads' events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    seen: Dict[str, List[str]] = {}
    for plane in data.planes:
        seen[plane.name] = [line.name for line in plane.lines]
        if _is_device(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    lines[key] += [[e.name, float(e.start_ns),
                                    float(e.duration_ns)]
                                   for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            # threads' lines may share a name: number them apart
            for i, line in enumerate(plane.lines):
                host[f"{plane.name}/{i}/{line.name}"] = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]
    return {"devices": devices, "host": host, "lines": seen}


def window_of(trace: Dict) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) of the benchmark's window span, if recorded."""
    for events in trace["host"].values():
        for name, start, dur in events:
            if name == WINDOW_SPAN:
                return start, start + dur
    return None


def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to ``[lo, hi]``, sorted, disjoint."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merge(((s, s + d) for _, s, d in events),
                                       lo, hi))


def device_busy(trace: Dict, window: Tuple[float, float],
                devices: Optional[Sequence[str]] = None) -> Dict[str, float]:
    """Busy seconds per device plane (operations' union in the window)."""
    lo, hi = window
    names = devices if devices is not None else sorted(trace["devices"])
    return {d: busy_ns(trace["devices"][d]["ops"], lo, hi) * 1e-9
            for d in names}


def fused_seconds(trace: Dict, window: Tuple[float, float]) -> Tuple[float,
                                                                    int]:
    """Summed device time and count of the fused scoring programs whose
    execution started inside the window, over every device."""
    lo, hi = window
    total, calls = 0.0, 0
    for lines in trace["devices"].values():
        for name, start, dur in lines["modules"]:
            if lo <= start < hi and any(k in name for k in FUSED_MODULES):
                total += dur * 1e-9
                calls += 1
    return total, calls


def op_name(text: str) -> str:
    """An operation's name: a TPU trace names each by its whole HLO
    instruction (``%fusion.3 = f32[16,8192]{...} fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: Dict, window: Tuple[float, float],
            n: int = 10) -> List[List]:
    """The device operations that took most time, summed over devices."""
    lo, hi = window
    by_name: Dict[str, float] = {}
    for lines in trace["devices"].values():
        for text, start, dur in lines["ops"]:
            if lo <= start < hi:
                name = op_name(text)
                by_name[name] = by_name.get(name, 0.0) + dur * 1e-9
    return [[k, v] for k, v in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Dict, window: Tuple[float, float],
              n: int = 10) -> List[List]:
    """The longest gaps in which no device of the trace was busy, each
    labelled with the host event that overlaps it most."""
    lo, hi = window
    busy = merge(((s, s + d) for lines in trace["devices"].values()
                  for _, s, d in lines["ops"]), lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:n]
    out = []
    for length, a, b in gaps:
        best, label = 0.0, "untraced host work"
        for events in trace["host"].values():
            for name, start, dur in events:
                if name == WINDOW_SPAN:
                    continue
                overlap = min(b, start + dur) - max(a, start)
                if overlap > best:
                    best, label = overlap, name
        out.append([label, length * 1e-9])
    return out


class TraceError(RuntimeError):
    """The trace does not show what every cell's window does on the chip."""


def require(summary: Optional[Dict], chips: int) -> Dict:
    """``summary``, if it shows the window, operations on each of the
    ``chips`` devices and at least one fused scoring program; every cell's
    window scores on each of its chips, so a trace that shows less is
    named otherwise than this reduction reads, and must not pass for an
    idle device."""
    if summary is None:
        raise TraceError(f"no {WINDOW_SPAN!r} span in the trace")
    seen = f"; planes and lines found: {summary.get('lines')}"
    planes = len(summary["busy_by_device"])
    if summary["devices_with_ops"] < chips:
        raise TraceError(
            f"{summary['devices_with_ops']} of {chips} device(s) ran an "
            f"operation on an {OPS_LINE!r} line in the window ({planes} "
            f"device plane(s) found){seen}")
    if summary["fused_calls"] < 1:
        raise TraceError(f"no {MODULES_LINE!r} event named like "
                         f"{FUSED_MODULES} in the window{seen}")
    return summary


def summarize(trace: Dict, chips: int) -> Optional[Dict]:
    """Every trace number a cell reports, or ``None`` without a window."""
    window = window_of(trace)
    if window is None:
        return None
    devices = sorted(trace["devices"], key=lambda d: int(d.rsplit(":", 1)[1]))
    used = devices[:chips]
    busy = device_busy(trace, window, used)
    window_s = (window[1] - window[0]) * 1e-9
    fused_s, fused_calls = fused_seconds(trace, window)
    mean_busy = sum(busy.values()) / len(busy) if busy else 0.0
    return {
        "window_s": window_s,
        "busy_s": mean_busy,
        "busy_by_device": busy,
        "devices_with_ops": sum(1 for v in busy.values() if v > 0),
        "idle_pct": (100.0 * (1.0 - mean_busy / window_s)
                     if window_s > 0 and busy else None),
        "fused_s": fused_s,
        "fused_calls": fused_calls,
        "device_ops": top_ops(trace, window),
        "idle_gaps": idle_gaps(trace, window),
        "lines": trace.get("lines"),
    }
