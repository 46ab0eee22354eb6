"""The system under test, as the benchmark drives it.

Everything that touches the program lives here: building the hardware
profiles from a configuration's constants, the serving entry
(``DesignCalculatorService``), turning plain questions into program
requests, warming the compiled shapes a cell's traffic can produce, and
reading the program's counters (``ServiceStats``, the ``packed_spec``
segment cache, the fused trace count).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from bench import traffic as tr
from bench.designs import ProgramSpecs
from bench.reference import Design, Workload


def pow2(n: int, floor: int = 16) -> int:
    return max(1 << max(int(n) - 1, 0).bit_length(), floor)


class System:
    """One ``DesignCalculatorService`` on a configuration's profiles."""

    def __init__(self, config: Dict, chips: int) -> None:
        from repro.core.hardware import analytical_profile
        from repro.serving import DesignCalculatorService
        self.config = config
        self.profiles = {name: analytical_profile(name, **constants)
                         for name, constants in config["hardware"].items()}
        self.svc = DesignCalculatorService(list(self.profiles.values()),
                                           scoring_shards=chips)
        self.specs = ProgramSpecs()
        self._workloads: Dict[Workload, object] = {}
        self.mix = tr.config_mix(config)

    def close(self) -> None:
        self.svc.stop()

    # -- plain inputs -> program inputs --------------------------------------
    def workload(self, wl: Workload):
        program = self._workloads.get(wl)
        if program is None:
            from repro.core.synthesis import Workload as ProgramWorkload
            program = ProgramWorkload(
                n_entries=wl.n_entries, n_queries=wl.n_queries,
                key_bytes=wl.key_bytes, value_bytes=wl.value_bytes,
                zipf_alpha=wl.zipf_alpha, selectivity=wl.selectivity)
            self._workloads[wl] = program
        return program

    def question_request(self, q: tr.Question, session: str):
        """The program's arguments for one question, built ahead of time;
        returns a callable that submits it."""
        svc, mix = self.svc, self.mix
        spec = self.specs.spec(q.design)
        wl = self.workload(q.workload)
        hw = self.profiles[q.hw]
        if q.kind == "design":
            variant = self.specs.spec(q.variant)
            return lambda: svc.submit_design(spec, variant, wl, hw, mix,
                                             session=session)
        if q.kind == "hardware":
            new_hw = self.profiles[q.new_hw]
            return lambda: svc.submit_hardware(spec, wl, hw, new_hw, mix,
                                               session=session)
        new_wl = self.workload(q.new_workload)
        return lambda: svc.submit_workload(spec, wl, new_wl, hw, mix,
                                           session=session)

    def sweep_request(self, designs: Sequence[Design],
                      points: Sequence[Tuple[Workload, Dict]], hw: str):
        specs = [self.specs.spec(d) for d in designs]
        wls = [self.workload(w) for w, _ in points]
        mixes = [dict(m) for _, m in points]
        profile = self.profiles[hw]
        return lambda: self.svc.submit_sweep(specs, wls, profile, mixes)

    def search_request(self, params: Dict, search_seed: int,
                       start: Sequence[Design]):
        wl = self.workload(tr.base_workload(self.config))
        profile = self.profiles[params["hardware"]]
        seeds = [self.specs.spec(d) for d in start]
        kwargs = {k: params[k] for k in ("population", "generations",
                                         "refine_top", "refine_steps")}
        return lambda: self.svc.submit_search(
            wl, profile, self.mix, budget_designs=int(params["budget"]),
            seed=search_seed, seeds=seeds, **kwargs)

    # -- counters --------------------------------------------------------------
    def counters(self) -> Dict[str, float]:
        from repro.core import batchcost, devicecost
        out = dict(self.svc.stats())
        info = batchcost.cache_info()["packed_spec"]
        out["packed_spec_hits"] = info.hits
        out["packed_spec_misses"] = info.misses
        out["fused_traces"] = devicecost.trace_count()
        return out

    @staticmethod
    def clear_memos() -> None:
        """Drop every packing and synthesis memo; compiled programs and
        device parameter banks stay."""
        from repro.core import batchcost
        batchcost.clear_caches()

    # -- warm-up ---------------------------------------------------------------
    def warm_frontier_shapes(self, max_designs: int,
                             max_records_per_design: int) -> int:
        """Compile the flat fused scorer for every (record bucket,
        design bucket) a window of up to ``max_designs`` designs per
        profile group can produce."""
        from repro.core import devicecost
        mid = devicecost.model_id("random_memory_access")
        hw = next(iter(self.profiles.values()))
        shapes = 0
        n_pad = 16
        while n_pad <= pow2(max_designs):
            lo = 16 if n_pad == 16 else pow2(n_pad // 2 * 8)
            rec = lo
            while rec <= pow2(n_pad * max_records_per_design):
                self._score_frontier_shape(devicecost, hw, mid, rec, n_pad)
                shapes += 1
                rec *= 2
            n_pad *= 2
        return shapes

    @staticmethod
    def _score_frontier_shape(devicecost, hw, mid: int, rec: int,
                              n_pad: int) -> None:
        n_seg = min(n_pad, rec // 8)
        tiles = np.minimum(np.arange(rec // 8) * n_seg // (rec // 8),
                           n_seg - 1).astype(np.int32)
        devicecost.score_frontier(np.full(rec, mid, np.int32),
                                  np.ones(rec, np.float32),
                                  np.ones(rec, np.float32), tiles,
                                  n_pad if n_pad > 16 else n_seg, hw,
                                  shard=False)

    def warm_sweep_shapes(self, n_points: int,
                          designs_per_call: Iterable[int],
                          devices: List) -> int:
        """Compile the sweep scorer for every record chunk bucket at each
        design bucket a sweep group can reach, on each scoring device."""
        from repro.core import devicecost
        mid = devicecost.model_id("random_memory_access")
        hw = next(iter(self.profiles.values()))
        chunk = devicecost.sweep_chunk(n_points)
        shapes = 0
        for n_designs in sorted(set(designs_per_call)):
            rec = 16
            while rec <= pow2(chunk):
                tiles = np.minimum(np.arange(rec // 8),
                                   n_designs - 1).astype(np.int32)
                for dev in devices:
                    devicecost.score_sweep(
                        np.full(rec, mid, np.int32),
                        np.ones((n_points, rec), np.float32),
                        np.ones((n_points, rec), np.float32), tiles,
                        n_designs, hw, shard=False, device=dev)
                    shapes += 1
                rec *= 2
        return shapes


def program_devices(chips: int) -> List:
    """The devices the service's scoring shards dispatch to (``None`` for
    the one-device in-thread path)."""
    if chips <= 1:
        return [None]
    import jax
    return list(jax.local_devices()[:chips])
