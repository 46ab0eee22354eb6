"""Chip smoke test: the served what-if path on a TPU.

    python chip_smoke.py               # one chip: service phases + kernels
    python chip_smoke.py --four-chips  # sharded sweep scoring across chips

One chip.  A ``DesignCalculatorService`` on the analytical profiles
HW1-HW3 answers, phase by phase:

  (a) the six interactive what-if questions of the load benchmark, on a
      100k-entry workload and its Zipf-skewed copy, hardware swaps
      included;
  (b) one 4096-design x 8-workload sweep;
  (c) one population search at a 256-design budget;
  (d) one design completion.

The window runs twice: a warm-up pass that compiles, then a measured
pass in which the fused scorer must not retrace.  Every answer must come
from the fused engine with every fallback and degradation counter at
zero, and agree with the scalar expert system
(``synthesis.cost_workload``) to the fused tier's 1e-6 relative error.
Then the four access-primitive Pallas kernels compile to Mosaic
(``interpret=False``) at the kernel benchmark's widths and must equal
their oracles exactly.

Four chips.  Only the multi-device path and what it is compared with:
the sweep of (b) scored by the pmap path (``shard=True``) must equal the
flat one-device call bit for bit, and a service with one scoring shard
per chip must answer it with parts on more than one chip.

Every phase prints its compile seconds, records scored and wall
seconds.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed
on a TPU; any failure, or a backend that is not a TPU, exits nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from benchmarks.common import enable_compile_cache  # noqa: E402

#: the fused tier's documented agreement with the scalar oracle
ORACLE_RTOL = 1e-6
#: sweep cells re-costed by the scalar oracle
SWEEP_SAMPLES = 64
SWEEP_DESIGNS, SWEEP_POINTS = 4096, 8
SEARCH_BUDGET = 256
#: upper bound on any one answer (the first one compiles)
ANSWER_TIMEOUT_S = 900.0


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_err(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


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


class Phase:
    """Times one phase and prints its line: compile seconds, records
    scored, wall seconds."""

    def __init__(self, clock: CompileClock, label: str) -> None:
        self.clock, self.label = clock, label
        self.records = 0
        self.extra = ""

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        self.c0 = self.clock.seconds
        return self

    def __exit__(self, exc_type, *_) -> None:
        if exc_type is None:
            compile_s = self.clock.seconds - self.c0
            print(f"  {self.label:<24} compile_s={compile_s:.3f}"
                  f"  records={self.records}"
                  f"  wall_s={time.perf_counter() - self.t0:.3f}{self.extra}",
                  flush=True)


# ---------------------------------------------------------------------------
# one chip: the service window
# ---------------------------------------------------------------------------
def _frontier_records(specs, workload, mix=None) -> int:
    from repro.core import batchcost
    return int(len(batchcost.pack_frontier(list(specs), workload, mix).ids))


def _interactive(svc, questions, clock, report) -> None:
    from repro.core.synthesis import cost_workload
    submit = {"design": svc.submit_design, "hardware": svc.submit_hardware,
              "workload": svc.submit_workload}
    pairs = []      # (baseline, variant) as cost_workload arguments
    for kind, spec, *rest in questions:
        if kind == "design":
            variant, wl, hw = rest
            pairs.append(((spec, wl, hw), (variant, wl, hw)))
        elif kind == "hardware":
            wl, hw, new_hw = rest
            pairs.append(((spec, wl, hw), (spec, wl, new_hw)))
        else:
            wl, new_wl, hw = rest
            pairs.append(((spec, wl, hw), (spec, new_wl, hw)))
    with Phase(clock, "(a) interactive x6") as ph:
        futures = [submit[q[0]](*q[1:]) for q in questions]
        answers = [f.result(timeout=ANSWER_TIMEOUT_S) for f in futures]
        ph.records = sum(_frontier_records([spec], wl)
                         for pair in pairs for spec, wl, _ in pair)
    for q, ans, (base, var) in zip(questions, answers, pairs):
        report.engine(f"(a) {q[0]} {q[1].name}", ans.engine)
        report.oracle(ans.baseline_seconds, cost_workload(*base))
        report.oracle(ans.variant_seconds, cost_workload(*var))


def _sweep(svc, inputs, hw, clock, report, rng) -> None:
    from repro.core import batchcost
    from repro.core.synthesis import cost_workload
    frontier, workloads, mixes = inputs
    with Phase(clock, f"(b) sweep {SWEEP_DESIGNS}x{SWEEP_POINTS}") as ph:
        ans = svc.submit_sweep(frontier, workloads, hw, mixes).result(
            timeout=ANSWER_TIMEOUT_S)
        sweep = batchcost.pack_sweep(frontier, workloads, mixes)
        ph.records = sweep.n_points * len(sweep.frontiers[0].ids)
    grid = np.asarray(ans.totals)
    check(grid.shape == (SWEEP_POINTS, SWEEP_DESIGNS),
          f"sweep grid shape {grid.shape}")
    check(bool(np.isfinite(grid).all()), "non-finite sweep cells")
    report.engine("(b) sweep", ans.engine)
    for _ in range(SWEEP_SAMPLES):
        i = int(rng.integers(SWEEP_POINTS))
        j = int(rng.integers(SWEEP_DESIGNS))
        report.oracle(grid[i, j], cost_workload(
            frontier[j], workloads[i], hw, mixes[i]))


def _search(svc, hw, clock, report) -> None:
    from repro.core import elements as el
    from repro.core.synthesis import Workload, cost_workload
    workload = Workload(n_entries=1_000_000, n_queries=100)
    mix = {"get": 80.0, "update": 20.0}
    with Phase(clock, f"(c) search {SEARCH_BUDGET}") as ph:
        # the popsearch benchmark's search, served
        res = svc.submit_search(
            workload, hw, mix, budget_designs=SEARCH_BUDGET,
            population=16, generations=200, refine_top=2, refine_steps=2,
            seed=10,
            seeds=[el.spec_btree(), el.spec_trie(), el.spec_csb_tree()]
        ).result(timeout=ANSWER_TIMEOUT_S)
        ph.extra = f"  designs_costed={res['designs_costed']}"
    check(res["designs_costed"] <= SEARCH_BUDGET,
          f"search spent {res['designs_costed']} > {SEARCH_BUDGET}")
    report.oracle(res["cost_s"],
                  cost_workload(res["design"], workload, hw, mix))


def _complete(svc, hw, clock, report) -> None:
    from repro.core.autocomplete import enumerate_frontier
    from repro.core.synthesis import Workload, cost_workload
    workload = Workload(n_entries=100_000, n_queries=100)
    mix = {"get": 100.0}
    with Phase(clock, "(d) complete") as ph:
        res = svc.submit_complete((), workload, hw, mix=mix,
                                  max_depth=2).result(
            timeout=ANSWER_TIMEOUT_S)
        ph.records = _frontier_records(
            enumerate_frontier((), None, None, 2, "auto"), workload, mix)
        ph.extra = f"  designs={res.explored}"
    report.engine("(d) complete", res.engine)
    report.oracle(res.cost_seconds,
                  cost_workload(res.spec, workload, hw, mix))


class Report:
    """Collects the engine tags and worst oracle error of a pass."""

    def __init__(self) -> None:
        self.engines = {}
        self.worst = 0.0

    def engine(self, label: str, engine: str) -> None:
        self.engines[label] = engine

    def oracle(self, got: float, want: float) -> None:
        self.worst = max(self.worst, rel_err(float(got), float(want)))


def _window(svc, profiles, inputs, clock, label: str) -> None:
    from benchmarks.load_bench import _interactive_questions
    from repro.core.synthesis import Workload
    h1, h2, h3 = profiles
    workload = Workload(n_entries=100_000, n_queries=100)
    skewed = dataclasses.replace(workload, zipf_alpha=1.5)
    report = Report()
    rng = np.random.default_rng(0)
    print(f"{label}:", flush=True)
    t0 = time.perf_counter()
    _interactive(svc, _interactive_questions(workload, skewed, h1, h2),
                 clock, report)
    _sweep(svc, inputs, h3, clock, report, rng)
    _search(svc, h3, clock, report)
    _complete(svc, h3, clock, report)
    print(f"  window wall_s={time.perf_counter() - t0:.3f}"
          f"  worst_oracle_rel_err={report.worst:.3e}", flush=True)
    not_fused = {k: v for k, v in report.engines.items() if v != "fused"}
    check(not not_fused, f"answers not from the fused engine: {not_fused}")
    check(report.worst <= ORACLE_RTOL,
          f"worst oracle error {report.worst:.3e} > {ORACLE_RTOL}")


def _check_stats(stats) -> None:
    degraded = {k: stats[k] for k in ("fallback_flat", "fallback_grouped",
                                      "engine_degraded", "nonfinite_groups",
                                      "failed")}
    print(f"service stats: questions={stats['questions']} "
          f"answered={stats['answered']} score_calls={stats['score_calls']} "
          f"shard_dispatches={stats['shard_dispatches']} {degraded}",
          flush=True)
    check(not any(degraded.values()), f"fallback counters fired: {degraded}")
    check(stats["answered"] == stats["questions"],
          f"answered {stats['answered']} of {stats['questions']}")


def one_chip(clock: CompileClock) -> None:
    from benchmarks.device_scaling import _sweep_inputs
    from repro.core import devicecost
    from repro.core.hardware import hw1, hw2, hw3
    from repro.serving import DesignCalculatorService

    profiles = (hw1(), hw2(), hw3())
    inputs = _sweep_inputs(SWEEP_DESIGNS, SWEEP_POINTS)
    with DesignCalculatorService(list(profiles)) as svc:
        _window(svc, profiles, inputs, clock, "warm-up pass")
        traces = devicecost.trace_count()
        _window(svc, profiles, inputs, clock, "measured pass")
        retraced = devicecost.trace_count() - traces
        print(f"fused traces: {traces} in warm-up, {retraced} in the "
              f"measured pass", flush=True)
        check(retraced == 0, f"{retraced} fused compiles in the window")
        _check_stats(svc.stats())
    kernels(clock)


# ---------------------------------------------------------------------------
# one chip: the Pallas kernels, compiled to Mosaic
# ---------------------------------------------------------------------------
def _compiled(clock: CompileClock, label: str, fn, *args,
              mosaic: bool = True):
    """Compile ``fn`` for ``args`` ahead of time, check the Mosaic kernel
    is in it (``mosaic``), and run it."""
    import jax
    with Phase(clock, label) as ph:
        compiled = jax.jit(fn).lower(*args).compile()
        check(not mosaic or "tpu_custom_call" in compiled.as_text(),
              f"{label}: no Mosaic kernel in the compiled program")
        out = jax.block_until_ready(compiled(*args))
        ph.records = sum(int(np.prod(a.shape)) for a in args)
    return jax.tree.map(np.asarray, out)


def kernels(clock: CompileClock, interpret: bool = False) -> None:
    """The access-primitive kernels at the kernel benchmark's widths."""
    import jax.numpy as jnp
    from repro.kernels.bloom_probe.ops import DEFAULT_COEFFS, bloom_probe
    from repro.kernels.bloom_probe.ref import bloom_probe_ref, build_filter
    from repro.kernels.hash_probe.ops import DEFAULT_A, hash_probe
    from repro.kernels.hash_probe.ref import (NOT_FOUND, build_table,
                                              hash_probe_ref)
    from repro.kernels.scan_filter.ops import scan_filter
    from repro.kernels.scan_filter.ref import scan_filter_ref
    from repro.kernels.sorted_search.ops import sorted_search
    from repro.kernels.sorted_search.ref import sorted_search_ref

    print("kernels (interpret=%s):" % interpret, flush=True)
    rng = np.random.default_rng(0)
    n, nq = 1 << 16, 1 << 12
    keys = np.sort(rng.integers(0, 1 << 30, n)).astype(np.int32)
    queries = rng.integers(0, 1 << 30, nq).astype(np.int32)
    keys_d, queries_d = jnp.asarray(keys), jnp.asarray(queries)

    got = _compiled(clock, "sorted_search",
                    lambda k, q: sorted_search(k, q, interpret=interpret),
                    keys_d, queries_d, mosaic=not interpret)
    check(np.array_equal(got, np.asarray(sorted_search_ref(keys_d,
                                                           queries_d))),
          "sorted_search differs from its oracle")

    ukeys = jnp.asarray(rng.permutation(keys))
    hits = jnp.asarray(np.concatenate([keys[: nq // 2], queries[nq // 2:]]))
    lo, hi = hits - 1000, hits + 1000
    pos, cnt = _compiled(
        clock, "scan_filter",
        lambda k, q, a, b: scan_filter(k, q, a, b, interpret=interpret),
        ukeys, hits, lo, hi, mosaic=not interpret)
    want_pos, want_cnt = scan_filter_ref(ukeys, hits, lo, hi)
    check(np.array_equal(pos, np.asarray(want_pos))
          and np.array_equal(cnt, np.asarray(want_cnt)),
          "scan_filter differs from its oracle")

    s_bits, cap = 10, 16
    tkeys = rng.choice(1 << 24, 8000, replace=False).astype(np.int64)
    tvals = rng.integers(1, 1 << 30, 8000).astype(np.int32)
    tk, tv = build_table(tkeys, tvals, s_bits, DEFAULT_A, cap)
    probes = np.concatenate([tkeys[: nq // 2].astype(np.int32),
                             queries[nq // 2:]])
    found, val = _compiled(
        clock, "hash_probe",
        lambda a, b, q: hash_probe(a, b, q, s=s_bits, interpret=interpret),
        jnp.asarray(tk), jnp.asarray(tv), jnp.asarray(probes),
        mosaic=not interpret)
    want_pos, want_val = hash_probe_ref(tk, tv, probes, DEFAULT_A, s_bits)
    check(np.array_equal(found, want_pos != NOT_FOUND)
          and np.array_equal(val, want_val),
          "hash_probe differs from its oracle")

    s_bloom, k_hash = 16, 3
    words = build_filter(tkeys, DEFAULT_COEFFS[:k_hash], s_bloom)
    member = _compiled(
        clock, "bloom_probe",
        lambda w, q: bloom_probe(w, q, s=s_bloom, num_hashes=k_hash,
                                 interpret=interpret),
        jnp.asarray(words), jnp.asarray(probes), mosaic=not interpret)
    check(np.array_equal(member, bloom_probe_ref(
        words, probes, DEFAULT_COEFFS[:k_hash], s_bloom)),
        "bloom_probe differs from its oracle")
    print("kernels: all four exact against their oracles", flush=True)


# ---------------------------------------------------------------------------
# four chips: sharded sweep scoring vs the flat call
# ---------------------------------------------------------------------------
def four_chips(clock: CompileClock) -> None:
    import jax
    from benchmarks.device_scaling import _sweep_inputs
    from repro.core import batchcost, devicecost
    from repro.core.hardware import hw3
    from repro.core.synthesis import cost_workload
    from repro.serving import DesignCalculatorService

    n_dev = len(jax.devices())
    check(n_dev > 1, f"--four-chips needs several devices, found {n_dev}")
    hw = hw3()
    frontier, workloads, mixes = _sweep_inputs(SWEEP_DESIGNS, SWEEP_POINTS)
    sweep = batchcost.pack_sweep(frontier, workloads, mixes)
    cells = sweep.n_points * len(sweep.frontiers[0].ids)
    for rep in ("cold", "warm"):
        with Phase(clock, f"flat 1 device ({rep})") as ph:
            flat = sweep.score(hw, shard=False)
            ph.records = cells
        with Phase(clock, f"pmap {n_dev} devices ({rep})") as ph:
            check(sweep._sharded_arrays(True) is not None,
                  "the sweep does not fit the pmap path")
            sharded = sweep.score(hw, shard=True)
            ph.records = cells
    same = bool(np.array_equal(sharded, flat))
    print(f"sharded == flat bit for bit: {same} "
          f"(max abs diff {float(np.max(np.abs(sharded - flat))):.3e})",
          flush=True)
    check(same, "the sharded sweep differs from the flat call")
    rng = np.random.default_rng(0)
    worst = max(rel_err(flat[i, j], cost_workload(frontier[j], workloads[i],
                                                  hw, mixes[i]))
                for i, j in zip(rng.integers(SWEEP_POINTS, size=SWEEP_SAMPLES),
                                rng.integers(SWEEP_DESIGNS,
                                             size=SWEEP_SAMPLES)))
    print(f"worst oracle rel err over {SWEEP_SAMPLES} cells: {worst:.3e}",
          flush=True)
    check(worst <= ORACLE_RTOL, f"oracle error {worst:.3e} > {ORACLE_RTOL}")

    with DesignCalculatorService([hw], scoring_shards=n_dev) as svc:
        for rep in ("cold", "warm"):
            with Phase(clock, f"service {n_dev} shards ({rep})") as ph:
                ans = svc.submit_sweep(frontier, workloads, hw, mixes).result(
                    timeout=ANSWER_TIMEOUT_S)
                ph.records = cells
            check(ans.engine == "fused", f"service engine {ans.engine!r}")
            check(bool(np.array_equal(np.asarray(ans.totals), flat)),
                  "the shard pool's merged grid differs from the flat call")
        stats = svc.stats()
    used = sorted({k[2] for k in devicecost._BANK_REPLICAS.keys()
                   if len(k) == 3 and k[1] == "device"})
    print(f"shard pool parts ran on devices {used}", flush=True)
    _check_stats(stats)
    check(stats["shard_dispatches"] > 0, "no shard dispatches")
    check(len(used) > 1, f"parts ran on devices {used} only")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-device path and its baseline")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        print(f"FAILED: no TPU (JAX backend is {dev.platform})",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(clock)
    except Exception as exc:
        print(f"FAILED: {type(exc).__name__}: {exc}", file=sys.stderr)
        raise
    print(f"all checks passed in {time.perf_counter() - t0:.1f}s "
          f"({clock.seconds:.1f}s compiling)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
