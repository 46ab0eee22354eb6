"""The three shapes of cell: set-up, measured window, and what to check.

Each cell kind matches a traffic ``kind`` (:mod:`bench.traffic`):

* :class:`WhatIfCell` -- open-loop what-if questions from designer
  sessions; reports due-to-answer latency percentiles;
* :class:`SweepCell` -- closed-loop (designs x points) sweeps; reports
  cells answered per second;
* :class:`SearchCell` -- closed-loop design searches over a fixed list of
  search seeds; reports seconds per search.

Set-up compiles every shape the window can produce, then drops every
packing and synthesis memo (:meth:`bench.system.System.clear_memos`), so
the window packs its designs itself and compiles nothing.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import drive
from bench import traffic as tr
from bench.check import Checks, Comparison
from bench.designs import from_spec
from bench.system import System, program_devices
from bench.work import SweepWork

#: an answer that has not come a minute after the window closed never will
WAIT_S = 60.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cell:
    def __init__(self, system: System, config: Dict, params: Dict,
                 seed: int, seconds: float, chips: int) -> None:
        self.system, self.config, self.params = system, config, params
        self.seed, self.seconds, self.chips = seed, seconds, chips
        self.slots: List[drive.Outcome] = []
        self.t0 = self.end = 0.0

    @property
    def answered(self) -> List[drive.Outcome]:
        return [s for s in self.slots if s.ok]

    def unanswered(self) -> int:
        return sum(1 for s in self.slots if s.done is None)

    def check_rng(self, *stream: int) -> np.random.Generator:
        return tr.rng_for(self.seed, tr.STREAM_CHECK, *stream)

    def work_bytes(self) -> Optional[int]:
        return None

    def extra_checks(self, checks: Checks, counters: Dict) -> None:
        pass


class WhatIfCell(Cell):
    """Designer sessions on an open loop at a fixed rate."""

    def setup(self) -> None:
        p, svc = self.params, self.system.svc
        questions = tr.whatif_questions(self.config, p, self.seed,
                                        self.seconds)
        names = [svc.session(f"designer-{s}").name
                 for s in range(int(p["sessions"]))]
        self.items = [(q, self.system.question_request(q, names[q.session]))
                      for q in questions]
        shapes = self.system.warm_frontier_shapes(
            int(p["warm_designs"]), int(p["warm_records_per_design"]))
        warm = tr.whatif_questions(self.config, p, self.seed,
                                   float(p["warm_seconds"]),
                                   stream=tr.STREAM_WARM)
        warm_names = [svc.session(f"warm-{s}").name
                      for s in range(int(p["sessions"]))]
        for q in warm:
            self.system.question_request(q, warm_names[q.session])().result(
                timeout=WAIT_S)
        log(f"set-up: {len(self.items)} questions due in the window, "
            f"{shapes} flat scorer shapes and {len(warm)} warm-up "
            f"questions")

    def window(self) -> Dict[str, float]:
        self.slots, self.t0 = drive.open_loop(
            self.items, lambda it: it[0].due_s, lambda it: it[1]())
        self.end = drive.wait_open(self.slots,
                                   self.t0 + self.seconds + WAIT_S)
        lat = drive.latencies_ms(self.slots, self.end)
        late = drive.lateness_ms(self.slots)
        log(f"window: {len(self.slots)} questions, "
            f"{len(self.answered)} answered; generator lateness ms "
            f"p50={drive.percentile(late, 50):.3f} "
            f"p99={drive.percentile(late, 99):.3f} "
            f"max={float(late.max()) if len(late) else 0.0:.3f}")
        return {"whatif_p50_ms": drive.percentile(lat, 50),
                "whatif_p95_ms": drive.percentile(lat, 95)}

    def comparisons(self) -> List[Comparison]:
        ok = self.answered
        out = []
        for i in tr.sample(self.check_rng(), len(ok),
                           int(self.params["check_questions"])):
            q, ans = ok[i].item[0], ok[i].value
            mix = self.system.mix
            out.append(Comparison(ans.baseline_seconds, q.design,
                                  q.workload, q.hw, mix))
            if q.kind == "design":
                var = (q.variant, q.workload, q.hw)
            elif q.kind == "hardware":
                var = (q.design, q.workload, q.new_hw)
            else:
                var = (q.design, q.new_workload, q.hw)
            out.append(Comparison(ans.variant_seconds, *var, mix))
        return out


class SweepCell(Cell):
    """Clients submitting fresh sweeps back to back."""

    def setup(self) -> None:
        from repro.serving.shards import ScoringShardPool
        p, system = self.params, self.system
        self.source = tr.design_source(self.config)
        for level in self.source.levels():
            system.specs.element(level)
        self.points = tr.sweep_points(self.config, p)
        n_points, n_designs = len(self.points), int(p["designs"])
        clients = int(p["clients"])
        pool = ScoringShardPool(self.chips)
        try:      # the per-device design counts the service can dispatch
            per_call = []
            for c in range(1, clients + 1):
                parts = pool.partitions(n_points * n_designs * c)
                per_call.append(math.ceil(n_designs * c / parts))
        finally:
            pool.close()
        shapes = system.warm_sweep_shapes(n_points, per_call,
                                          program_devices(self.chips))
        self.sweeps: Dict[Tuple[int, int], Tuple] = {}
        for c in range(clients):
            for k in range(int(p["prebuilt_per_client"])):
                self.sweeps[c, k] = self._build(c, k)
        warm = tr.sweep_designs(self.config, p, self.seed, 0, 0,
                                self.source, stream=tr.STREAM_WARM,
                                count=int(p["warm_designs"]))
        system.sweep_request(warm, self.points, p["hardware"])().result(
            timeout=WAIT_S)
        log(f"set-up: {len(self.sweeps)} sweeps of {n_designs} designs x "
            f"{n_points} points built, {shapes} sweep scorer shapes for "
            f"{per_call} designs per call")

    def _build(self, client: int, k: int) -> Tuple:
        designs = tr.sweep_designs(self.config, self.params, self.seed,
                                   client, k, self.source)
        return designs, self.system.sweep_request(
            designs, self.points, self.params["hardware"])

    def _make(self, client: int, k: int) -> Tuple:
        built = self.sweeps.get((client, k)) or self._build(client, k)
        return built[0], built[1]

    def window(self) -> Dict[str, float]:
        self.slots, self.t0, self.end = drive.closed_loop(
            int(self.params["clients"]), self._make, self.seconds, WAIT_S)
        cells = len(self.answered) * len(self.points) \
            * int(self.params["designs"])
        log(f"window: {len(self.slots)} sweeps, {len(self.answered)} "
            f"answered, {cells} cells in {self.end - self.t0:.3f} s")
        return {"sweep_cells_per_s": cells / (self.end - self.t0)}

    def comparisons(self) -> List[Comparison]:
        out = []
        self.malformed = 0
        shape = (len(self.points), int(self.params["designs"]))
        for n, slot in enumerate(self.answered):
            grid = np.asarray(slot.value.totals, dtype=np.float64)
            if grid.shape != shape or not np.isfinite(grid).all():
                self.malformed += 1
                continue
            rng = self.check_rng(n)
            k = int(self.params["check_cells_per_sweep"])
            rows = rng.integers(shape[0], size=k)
            cols = rng.integers(shape[1], size=k)
            for i, j in zip(rows.tolist(), cols.tolist()):
                wl, mix = self.points[i]
                out.append(Comparison(float(grid[i, j]), slot.item[j], wl,
                                      self.params["hardware"], mix))
        return out

    def extra_checks(self, checks: Checks, counters: Dict) -> None:
        checks.at_most("malformed_grids", self.malformed, 0)
        if self.chips > 1:
            checks.at_least("shard_dispatches", counters["shard_dispatches"],
                            1)

    def work_bytes(self) -> Optional[int]:
        work = SweepWork()
        return sum(work.sweep_bytes(s.item, self.points)
                   for s in self.answered)


class SearchCell(Cell):
    """One caller running the fixed list of searches back to back."""

    def setup(self) -> None:
        p = self.params
        self.seeds = tr.search_seeds(p, self.seed)
        start = tr.search_start_designs(p)
        self.requests = [self.system.search_request(p, s, start)
                         for s in self.seeds]
        # the window's own searches, once and one at a time as there:
        # every surrogate and scorer shape they reach compiles here (a
        # failed one fails again in the window, where it counts)
        self.rehearsal = []
        for request in self.requests:
            fut = request()
            self.rehearsal.append(fut.result(timeout=WAIT_S) if
                                  fut.exception(timeout=WAIT_S) is None
                                  else None)
        log(f"set-up: {len(self.seeds)} searches rehearsed")

    def _make(self, client: int, k: int) -> Optional[Tuple]:
        if k >= len(self.seeds):
            return None
        return self.seeds[k], self.requests[k]

    def window(self) -> Dict[str, float]:
        self.slots, self.t0, self.end = drive.closed_loop(
            1, self._make, self.seconds, WAIT_S)
        ok = self.answered
        same = sum(1 for s, r in zip(self.slots, self.rehearsal)
                   if s.ok and r is not None
                   and s.value["design"].chain == r["design"].chain)
        log(f"window: {len(ok)} of {len(self.seeds)} searches in "
            f"{self.end - self.t0:.3f} s; {same} chose the design their "
            f"rehearsal chose; seconds by search seed "
            f"{ {s.item: round(s.done - s.due, 3) for s in ok} }")
        return {"search_s": (self.end - self.t0) / max(len(ok), 1)}

    def comparisons(self) -> List[Comparison]:
        wl = tr.base_workload(self.config)
        return [Comparison(float(s.value["cost_s"]),
                           from_spec(s.value["design"]), wl,
                           self.params["hardware"], self.system.mix)
                for s in self.answered]

    def extra_checks(self, checks: Checks, counters: Dict) -> None:
        budget = int(self.params["budget"])
        spent = [int(s.value["designs_costed"]) for s in self.answered]
        checks.at_most("budget_overspend",
                       max([0] + [x - budget for x in spent]), 0)


KINDS = {"open_whatif": WhatIfCell, "closed_sweep": SweepCell,
         "closed_search": SearchCell}
