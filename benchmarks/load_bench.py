"""BENCH_load: sustained mixed-traffic load through the hardened server.

Drives hundreds of concurrent questions — closed-loop interactive
what-if clients plus bulk workload-sweep clients — through two serving
regimes at equal offered load:

1. **fifo** — the pre-hardening baseline (``lanes=False``): one
   unbounded-order queue, no priority, every future resolves when its
   whole coalescing window has scored.  Interactive latency rides on
   whatever bulk work shares (and precedes) the window.
2. **lanes** — the hardened regime: bounded priority lanes with
   weighted dequeue, at most ``bulk_per_window`` sweeps per coalescing
   window, interactive groups scored first and resolved eagerly.

Recorded per regime: per-lane p50/p95/p99 latency, questions/sec; the
acceptance bar is interactive p99 improving ``TARGET_P99_RATIO`` x under
lanes.  Three hardening behaviors are exercised and recorded alongside:

* **overload shedding** — a burst into a deliberately tiny bulk lane
  must shed with :class:`~repro.serving.admission.RejectedError`
  (never block, never deadlock); the shed rate lands in the row;
* **zero recompiles under load** — ``devicecost.trace_count`` must not
  move across the measured lanes drive (hardware swap stays a pure
  parameter-table swap even with concurrent mixed traffic);
* **warm restart** — the synthesis/packing memos are snapshotted
  (:meth:`~repro.serving.DesignCalculatorService.save_snapshot`), the
  packing layers are dropped, and the first question of a freshly
  started service is timed cold vs snapshot-restored; the bar is
  ``TARGET_WARM_SPEEDUP`` x.  Compiled executables are deliberately
  kept in both arms — a real restart pays XLA compilation identically
  either way, so the in-process A/B isolates exactly what the snapshot
  persists.

Interactive answers are spot-checked against the scalar ``cost_workload``
oracle (1e-6) after the drives.  Each full run appends one labelled
entry to experiments/bench/BENCH_load.json; ``run(smoke=True)`` pushes a
small mixed burst through the lanes regime in seconds — zero recompiles,
zero shed interactive requests, parity — without touching the
trajectory.  Standalone runs re-exec under the tcmalloc +
``xla_force_host_platform_device_count`` process tuning
(:func:`benchmarks.common.apply_process_tuning`).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.common import emit_trajectory

#: acceptance bar: interactive p99 (fifo) / interactive p99 (lanes)
TARGET_P99_RATIO = 3.0
#: acceptance bar: cold first-question / warm-restarted first-question
TARGET_WARM_SPEEDUP = 3.0


def _interactive_questions(workload, skewed, h1, h2) -> List[Tuple]:
    """A small cycle of cheap what-if questions (the interactive lane)."""
    from repro.core import elements as el, whatif
    b, hsh, skip = el.spec_btree(), el.spec_hash_table(), el.spec_skip_list()
    bloom = whatif.add_bloom_filters(el.spec_hash_table())
    return [
        ("design", b, el.spec_btree(fanout=40), workload, h1),
        ("hardware", hsh, workload, h1, h2),
        ("workload", skip, workload, skewed, h1),
        ("design", hsh, bloom, workload, h2),
        ("hardware", b, workload, h1, h2),
        ("workload", b, workload, skewed, h2),
    ]


def _bulk_sweep(n_specs: int, n_points: int, base_workload):
    """One deliberately heavy (designs x workloads) sweep (the bulk lane)."""
    from repro.core import elements as el
    specs = [el.spec_btree(fanout=8 + 2 * i, page=128 << (i % 3))
             for i in range(n_specs)]
    alphas = np.linspace(0.0, 1.5, n_points)
    workloads = [dataclasses.replace(base_workload, zipf_alpha=float(a))
                 for a in alphas]
    return specs, workloads


def _submit_interactive(service, q: Tuple):
    kind = q[0]
    if kind == "design":
        return service.submit_design(q[1], q[2], q[3], q[4])
    if kind == "hardware":
        return service.submit_hardware(q[1], q[2], q[3], q[4])
    return service.submit_workload(q[1], q[2], q[3], q[4])


def _drive(service, duration_s: float, n_interactive: int, n_bulk: int,
           questions: List[Tuple], sweep, bulk_hw) -> Dict:
    """Closed-loop mixed load for ``duration_s``; per-lane latencies."""
    from repro.serving import RejectedError, ServiceError
    out = {"interactive": [], "bulk": [], "shed_interactive": 0,
           "shed_bulk": 0, "errors": []}
    lock = threading.Lock()
    stop = threading.Event()
    specs, workloads = sweep

    def interactive_client(idx: int) -> None:
        i = idx
        while not stop.is_set():
            q = questions[i % len(questions)]
            i += 1
            t0 = time.perf_counter()
            try:
                _submit_interactive(service, q).result(timeout=120.0)
            except RejectedError:
                with lock:
                    out["shed_interactive"] += 1
                time.sleep(0.001)
                continue
            except ServiceError as exc:
                with lock:
                    out["errors"].append(repr(exc))
                continue
            with lock:
                out["interactive"].append(time.perf_counter() - t0)

    def bulk_client(idx: int) -> None:
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                service.submit_sweep(specs, workloads,
                                     bulk_hw).result(timeout=300.0)
            except RejectedError:
                with lock:
                    out["shed_bulk"] += 1
                time.sleep(0.001)
                continue
            except ServiceError as exc:
                with lock:
                    out["errors"].append(repr(exc))
                continue
            with lock:
                out["bulk"].append(time.perf_counter() - t0)

    threads = [threading.Thread(target=interactive_client, args=(i,),
                                daemon=True) for i in range(n_interactive)]
    threads += [threading.Thread(target=bulk_client, args=(i,),
                                 daemon=True) for i in range(n_bulk)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    out["wall_s"] = time.perf_counter() - t_start
    return out


def _percentiles(samples: List[float]) -> Dict[str, float]:
    if not samples:
        return {"p50": float("nan"), "p95": float("nan"),
                "p99": float("nan")}
    arr = np.asarray(samples) * 1e3   # -> milliseconds
    return {"p50": float(np.percentile(arr, 50)),
            "p95": float(np.percentile(arr, 95)),
            "p99": float(np.percentile(arr, 99))}


def _check_parity(service, questions: List[Tuple]) -> None:
    """Sampled answers under load-warmed caches vs the scalar oracle."""
    from repro.core import whatif
    oracle_fns = {"design": whatif.what_if_design,
                  "hardware": whatif.what_if_hardware,
                  "workload": whatif.what_if_workload}
    for q in questions[:3]:
        got = _submit_interactive(service, q).result(timeout=120.0)
        ref = oracle_fns[q[0]](*q[1:], engine="scalar")
        for attr in ("baseline_seconds", "variant_seconds"):
            g, r = getattr(got, attr), getattr(ref, attr)
            assert abs(g - r) <= 1e-6 * abs(r), (q[0], attr, g, r)


def _overload_probe(h1, workload) -> Tuple[int, int]:
    """Burst into a tiny bulk lane: sheds must reject, never deadlock."""
    from repro.serving import (DesignCalculatorService, RejectedError,
                               ServiceError)
    specs, workloads = _bulk_sweep(4, 3, workload)
    svc = DesignCalculatorService([h1], window_s=0.05, bulk_capacity=2,
                                  bulk_per_window=1)
    n_offered, shed, futures = 24, 0, []
    try:
        for _ in range(n_offered):
            try:
                futures.append(svc.submit_sweep(specs, workloads, h1))
            except RejectedError:
                shed += 1
        for fut in futures:
            try:
                fut.result(timeout=60)
            except ServiceError:
                pass
    finally:
        svc.stop()
    return shed, n_offered


def _forget_packing() -> None:
    """Drop exactly the layers a warm-restart snapshot persists (plus
    their synthesis feeders), keeping compiled executables: the cold/warm
    A/B then isolates the snapshot's contribution."""
    from repro.core import memo, templatecost
    from repro.core.synthesis import clear_synthesis_caches
    with memo.MEMO_LOCK:
        for name in ("packed_spec", "frontier", "sweep"):
            cache = memo.REGISTRY.get(name)
            if cache is not None:
                cache.clear()
        templatecost.clear_template_caches()
        clear_synthesis_caches()


def _first_question_s(h1, workload, n_specs: int, n_points: int,
                      snapshot_path: Optional[str]) -> Tuple[float, int]:
    """Start a fresh service (optionally warm-restored) on dropped packing
    caches and time its first sweep question, built from *fresh* spec and
    workload objects (no instance-level statics riding along)."""
    from repro.serving import DesignCalculatorService
    _forget_packing()
    specs, workloads = _bulk_sweep(n_specs, n_points, workload)
    svc = DesignCalculatorService([h1], window_s=0.001,
                                  snapshot_path=snapshot_path)
    try:
        t0 = time.perf_counter()
        svc.workload_sweep(specs, workloads, h1)
        elapsed = time.perf_counter() - t0
        restored = svc.stats()["snapshot_entries"]
    finally:
        svc.stop()
    return elapsed, restored


def _smoke(h1, h2, workload, skewed) -> None:
    """S5 smoke: a small mixed burst through the lanes regime — zero
    recompiles, zero dropped interactive requests, scalar parity."""
    from benchmarks.common import _print_table
    from repro.core import devicecost
    from repro.serving import DesignCalculatorService
    questions = _interactive_questions(workload, skewed, h1, h2)
    sweep = _bulk_sweep(6, 4, workload)
    svc = DesignCalculatorService([h1, h2], window_s=0.05,
                                  bulk_per_window=1)
    try:
        # warm pass compiles every shape the burst can produce
        for q in questions:
            _submit_interactive(svc, q).result(timeout=120.0)
        svc.submit_sweep(*sweep, h1).result(timeout=300.0)
        res = _drive(svc, 0.5, n_interactive=4, n_bulk=1,
                     questions=questions, sweep=sweep, bulk_hw=h1)
        traces_before = devicecost.trace_count()
        futures = [_submit_interactive(svc, q) for q in questions * 2]
        futures.append(svc.submit_sweep(*sweep, h1))
        for fut in futures:
            fut.result(timeout=60)
        recompiles = devicecost.trace_count() - traces_before
        _check_parity(svc, questions)
        stats = svc.stats()
    finally:
        svc.stop()
    assert recompiles == 0, \
        f"mixed burst recompiled the fused scorer {recompiles}x"
    assert res["shed_interactive"] == 0 and stats["shed_interactive"] == 0, \
        "interactive requests were shed under a small mixed burst"
    assert not res["errors"], res["errors"][:3]
    lat = _percentiles(res["interactive"])
    _print_table("BENCH_load [smoke — not persisted]", [{
        "interactive_served": len(res["interactive"]),
        "bulk_served": len(res["bulk"]),
        "interactive_p50_ms": lat["p50"],
        "interactive_p99_ms": lat["p99"],
        "recompiles": recompiles,
        "shed_interactive": stats["shed_interactive"],
    }])
    print("load smoke: zero recompiles, zero interactive sheds, parity ok")


def run(quick: bool = False, smoke: bool = False) -> None:
    import os
    import tempfile

    from repro.core import devicecost
    from repro.core.hardware import hw1, hw2
    from repro.core.synthesis import Workload
    from repro.serving import DesignCalculatorService

    workload = Workload(n_entries=100_000, n_queries=100)
    skewed = dataclasses.replace(workload, zipf_alpha=1.5)
    h1, h2 = hw1(), hw2()
    if smoke:
        _smoke(h1, h2, workload, skewed)
        return

    duration = 2.0 if quick else 4.0
    n_interactive, n_bulk = 8, 3
    # the bulk sweep must be *heavy*: its fused call is the thing
    # interactive requests hide behind in the FIFO baseline (~32k cells
    # is ~10-15 ms of scoring per call on the container CPU)
    n_specs, n_points = (384, 48) if quick else (512, 64)
    questions = _interactive_questions(workload, skewed, h1, h2)
    sweep = _bulk_sweep(n_specs, n_points, workload)

    # -- regime A: pre-hardening FIFO baseline ------------------------------
    fifo_svc = DesignCalculatorService([h1, h2], window_s=0.002,
                                       lanes=False)
    try:
        _drive(fifo_svc, min(duration / 2, 1.5), n_interactive, n_bulk,
               questions, sweep, h1)                  # warm + compile
        fifo = _drive(fifo_svc, duration, n_interactive, n_bulk,
                      questions, sweep, h1)
    finally:
        fifo_svc.stop()
    assert not fifo["errors"], fifo["errors"][:3]

    # -- regime B: hardened lanes, equal offered load -----------------------
    lanes_svc = DesignCalculatorService([h1, h2], window_s=0.002,
                                        bulk_per_window=1)
    try:
        _drive(lanes_svc, min(duration / 2, 1.5), n_interactive, n_bulk,
               questions, sweep, h1)                  # warm + compile
        traces_before = devicecost.trace_count()
        lanes = _drive(lanes_svc, duration, n_interactive, n_bulk,
                       questions, sweep, h1)
        recompiles = devicecost.trace_count() - traces_before
        _check_parity(lanes_svc, questions)
        lane_stats = lanes_svc.stats()
    finally:
        lanes_svc.stop()
    assert not lanes["errors"], lanes["errors"][:3]
    assert lanes["shed_interactive"] == 0, \
        "interactive lane shed under nominal load"
    assert recompiles == 0, \
        f"sustained mixed load recompiled the fused scorer {recompiles}x"

    shed, offered = _overload_probe(h1, workload)
    assert shed > 0, "overloading a 2-deep bulk lane shed nothing"

    # -- warm restart -------------------------------------------------------
    with tempfile.TemporaryDirectory() as tmp:
        snap = os.path.join(tmp, "memo.snapshot")
        keeper = DesignCalculatorService([h1], snapshot_path=snap,
                                         start=False)
        written = keeper.save_snapshot()      # caches are load-warm
        cold_s, _ = _first_question_s(h1, workload, n_specs, n_points,
                                      snapshot_path=None)
        warm_s, restored = _first_question_s(h1, workload, n_specs,
                                             n_points, snapshot_path=snap)
    assert restored > 0, "warm restart restored nothing from the snapshot"
    warm_speedup = cold_s / max(warm_s, 1e-12)

    fifo_i = _percentiles(fifo["interactive"])
    lanes_i = _percentiles(lanes["interactive"])
    lanes_b = _percentiles(lanes["bulk"])
    p99_ratio = fifo_i["p99"] / max(lanes_i["p99"], 1e-12)
    rows = [{
        "bench": "sustained_load",
        "duration_s": duration,
        "clients_interactive": n_interactive,
        "clients_bulk": n_bulk,
        "fifo_interactive_p50_ms": fifo_i["p50"],
        "fifo_interactive_p99_ms": fifo_i["p99"],
        "fifo_qps": (len(fifo["interactive"]) + len(fifo["bulk"]))
        / fifo["wall_s"],
        "lanes_interactive_p50_ms": lanes_i["p50"],
        "lanes_interactive_p95_ms": lanes_i["p95"],
        "lanes_interactive_p99_ms": lanes_i["p99"],
        "lanes_bulk_p50_ms": lanes_b["p50"],
        "lanes_bulk_p99_ms": lanes_b["p99"],
        "lanes_qps": (len(lanes["interactive"]) + len(lanes["bulk"]))
        / lanes["wall_s"],
        "interactive_p99_ratio": p99_ratio,
        "shed_rate_overloaded": shed / offered,
        "recompiles_under_load": recompiles,
        "score_calls": lane_stats["score_calls"],
        "snapshot_entries": written,
        "cold_first_question_s": cold_s,
        "warm_first_question_s": warm_s,
        "warm_restart_speedup": warm_speedup,
    }]
    # device scaling: questions/sec through the scoring-shard pool at 1
    # vs N devices (forced host devices in subprocess children on the
    # CPU, this process's own devices on an accelerator).  The >= 2x
    # bar is asserted inside serving_scaling_row where 4 devices can
    # scale and recorded as an explicit waiver otherwise.
    from benchmarks import device_scaling
    scaling = device_scaling.serving_scaling_row(quick)
    print(f"shard-routed serving at {device_scaling.BAR_DEVICES} devices"
          f" vs 1: {scaling['speedup_serving_4dev_vs_1dev']:.2f}x "
          f"({scaling['scaling_bar']})")
    rows[0].update(scaling)
    keys = list(rows[0].keys())
    print(f"interactive p99: fifo {fifo_i['p99']:.1f} ms -> lanes "
          f"{lanes_i['p99']:.1f} ms ({p99_ratio:.1f}x, target >= "
          f"{TARGET_P99_RATIO:.0f}x); warm restart {warm_speedup:.1f}x "
          f"(target >= {TARGET_WARM_SPEEDUP:.0f}x)")
    assert p99_ratio >= TARGET_P99_RATIO, \
        "priority lanes regressed below the interactive-p99 bar"
    assert warm_speedup >= TARGET_WARM_SPEEDUP, \
        "warm restart regressed below the first-question bar"
    emit_trajectory("BENCH_load", "PR7 device-routed serving tier",
                    rows, keys=keys)


if __name__ == "__main__":
    from benchmarks.common import apply_process_tuning
    apply_process_tuning()
    run()
