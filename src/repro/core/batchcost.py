"""Batch cost-synthesis engine: cost whole candidate frontiers per call.

The Data Calculator's promise (paper §4, Algorithm 1) is answering what-if
design questions in seconds by *synthesizing* cost.  The scalar path costs
one design at a time — ``AccessRecord.cost`` dispatches one
``predict_scalar`` per record, so a search over N candidates pays
N x records x models worth of per-call model-evaluation overhead.

This module compiles each synthesized :class:`CostBreakdown` into parallel
numpy arrays (Level-2 model id, size argument, weighted count) and scores
whole frontiers through one of two engines:

* ``engine="fused"`` (default): the packed frontier arrays go to
  :func:`repro.core.devicecost.score_frontier` — **one** jitted JAX call
  evaluating every record against device-resident parameter banks and
  reducing with a single ``segment_sum`` (the serving tier's
  :class:`repro.serving.shards.ScoringShardPool` splits big products
  across devices).
* ``engine="grouped"``: the PR-1 reference oracle — group records of all
  candidates by model and evaluate each Level-2 model's vectorized
  :meth:`FittedModel.predict` once per call (~14 predictions per
  frontier).  It matches the scalar path to 1e-9 relative; the fused
  engine matches it to 1e-6 (see devicecost's module docstring).

Public API
----------
``cost_many(specs, workload, hw, mix, engine="fused")``
    Totals for a frontier of specs under one workload/mix — the batched
    equivalent of ``[cost_workload(s, workload, hw, mix) for s in specs]``
    (matching it to float tolerance; argmin-compatible).
``pack_frontier(specs, workload, mix)``
    The hardware-independent packed arrays of a frontier; score the same
    :class:`PackedFrontier` against many profiles (what-if hardware) with
    zero re-synthesis and zero recompilation.  Construction is
    **template-vectorized** (:mod:`repro.core.templatecost`): chains never
    seen before are grouped by structural template and synthesized as
    batched numpy column ops — no per-design Python walk — while chains
    packed earlier splice their cached per-spec segments straight in
    (*incremental packing*; a re-packed identical frontier is one cache
    hit).
``concat_frontiers(parts)``
    Splice already-packed frontiers into one — hill-climb/beam rounds and
    ``whatif`` baseline+variant pairs compose retained frontiers instead
    of re-packing every design.
``pack_sweep(specs, workloads, mixes)`` / ``cost_sweep(...)``
    The **workload-sweep engine** (PR 5): a (designs x workloads) grid —
    a read/write-ratio, skew, selectivity or data-size continuum — packed
    whole as a grid over the shared workload-free template statics with
    per-workload geometry columns (:func:`repro.core.templatecost.
    pack_grid`: each point's frontier is a row of its group's buffers)
    and scored in ONE fused call
    (:func:`repro.core.devicecost.score_sweep`: bank gathers issued once
    for all workloads).  ``cost_sweep`` returns the ``[W, D]`` totals
    grid; hardware stays a pure parameter-table swap.
    ``concat_sweeps(parts)`` splices sweeps over the same points along
    the design axis (the serving coalescing primitive).
``compiled_operation(op, spec, workload)``
    The cached compiled form of one operation's breakdown through the
    *scalar* expert system — the per-design oracle the vectorized packer
    is tested against (and the ``cost_one`` fast path).
``clear_caches()``
    Drop every memo in the synthesis/packing stack (tests,
    element-library edits) — including the template, segment, frontier
    and sweep caches, and any cache registered via :func:`register_cache`.

All memo layers are thread-safe: the insertable dict caches (and the
interning/device-table state in :mod:`repro.core.devicecost`) share the
single re-entrant lock of :mod:`repro.core.memo`, so concurrent scoring
threads — the :mod:`repro.serving` access pattern — cannot corrupt
hit/miss accounting, and ``clear_caches()``/``cache_info()`` drain and
snapshot every layer atomically.  Misses still compute outside the lock
(two racing threads may redundantly pack one frontier; both store equal
values).

Caching layers (all keyed on hashable, frozen inputs — hardware is *not*
part of any key, so re-costing a frontier on new hardware touches no
synthesis code at all; the full memo map lives in
``docs/cost_pipeline.md`` and the key invariants are asserted by
``tests/test_cache_keys.py``):

1. ``chain_statics`` / ``segment_statics`` in
   :mod:`repro.core.templatecost` — the workload-FREE template half of
   every segment (level structure, regions, record model-ids), keyed on
   (chain, depth signature) and (template, ops); a workload sweep
   re-derives only numeric columns.  ``chain_geometry`` layers one
   workload's numerics on top, and the scalar ``instantiate`` twin lives
   in :mod:`repro.core.synthesis`.
2. The per-(n_nodes, zipf_alpha) skew weights and per-template
   ``symbolic_breakdown`` schemas, memoized in synthesis.
3. The *segment cache* here: each spec's mix-weighted, tile-padded
   (ids, sizes, weights) arrays per (chain, workload, mix) — populated in
   batch by the vectorized packer, reused record-for-record by later
   frontiers containing the same chain.  ``pack_frontier`` only: a sweep
   packs as a grid and cuts, looks up and stores no segment.
4. The *frontier cache*: whole packed frontiers per (chains, workload,
   mix) — and the *sweep cache*: whole (designs x workloads) grids per
   (chains, points) — the steady-state what-if-serving hit paths.
5. ``compiled_operation`` per (op, chain, workload) — scalar-oracle path
   only.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import devicecost, memo as memo_module, spans, templatecost
from repro.core.devicecost import _MODEL_NAMES, model_id as _model_id
from repro.core.elements import DataStructureSpec, Element
from repro.core.hardware import HardwareProfile
from repro.core.memo import MEMO_LOCK, CacheInfo, DictCache as _DictCache
from repro.core.synthesis import (CostBreakdown, Workload,
                                  clear_synthesis_caches,
                                  synthesize_operation)


@dataclasses.dataclass(frozen=True)
class CompiledBreakdown:
    """A CostBreakdown flattened into parallel arrays (one row per record)."""

    model_ids: np.ndarray    # int32  [R] — interned Level-2 model ids
    sizes: np.ndarray        # float64 [R] — primitive size arguments
    counts: np.ndarray       # float64 [R] — record weights

    @property
    def n_records(self) -> int:
        return len(self.sizes)

    def total(self, hw: HardwareProfile) -> float:
        """Scalar-equivalent total, one predict per distinct model."""
        out = 0.0
        for mid in np.unique(self.model_ids):
            mask = self.model_ids == mid
            y = _predict_padded(hw.model(_MODEL_NAMES[mid]), self.sizes[mask])
            out += float(np.dot(self.counts[mask], y))
        return out


#: largest padded predict shape; bigger inputs evaluate in _MAX_BUCKET chunks
_MAX_BUCKET = 4096


def _predict_padded(model, sizes: np.ndarray) -> np.ndarray:
    """model.predict with the input padded to a power-of-two length.

    Frontier sizes vary call to call; un-jitted jax ops compile per shape,
    so raw variable-length predicts would recompile XLA kernels on almost
    every search.  Bucketing lengths to powers of two — capped at
    ``_MAX_BUCKET``, with larger inputs evaluated in full chunks — bounds
    the shape set to ~9 shapes per model (compile once, reuse forever).
    Padding slots carry x=1.0 and are sliced off — per-record outputs are
    unchanged because every model evaluates records elementwise /
    row-independently.
    """
    n = len(sizes)
    if n == 0:
        return np.zeros(0, dtype=np.float64)
    if n > _MAX_BUCKET:
        return np.concatenate([
            _predict_padded(model, sizes[i:i + _MAX_BUCKET])
            for i in range(0, n, _MAX_BUCKET)])
    bucket = max(1 << (n - 1).bit_length(), 16)
    if bucket == n:
        padded = sizes
    else:
        padded = np.ones(bucket, dtype=sizes.dtype)
        padded[:n] = sizes
    return np.asarray(model.predict(padded)[:n], dtype=np.float64)


def compile_breakdown(cb: CostBreakdown) -> CompiledBreakdown:
    n = len(cb.records)
    model_ids = np.empty(n, dtype=np.int32)
    sizes = np.empty(n, dtype=np.float64)
    counts = np.empty(n, dtype=np.float64)
    for i, rec in enumerate(cb.records):
        model_ids[i] = _model_id(rec.level2)
        sizes[i] = rec.size
        counts[i] = rec.count
    model_ids.setflags(write=False)
    sizes.setflags(write=False)
    counts.setflags(write=False)
    return CompiledBreakdown(model_ids, sizes, counts)


@functools.lru_cache(maxsize=65536)
def _compiled_operation(op: str, chain: Tuple[Element, ...],
                        workload: Workload) -> CompiledBreakdown:
    spec = DataStructureSpec("batch", chain)
    return compile_breakdown(synthesize_operation(op, spec, workload))


def compiled_operation(op: str, spec: DataStructureSpec,
                       workload: Workload) -> CompiledBreakdown:
    """Synthesize + compile one operation, memoized on (op, chain, workload)."""
    return _compiled_operation(op, spec.chain, workload)


#: per-spec packed segments — (chain, workload, mix) -> (ids, sizes, weights);
#: snapshot-enabled: segments are the expensive hardware-free synthesis
#: product a warm-restarted service wants back first
_segment_cache = _DictCache(maxsize=65536, name="packed_spec",
                            snapshot=True)
#: whole packed frontiers — (chains, workload, mix) -> PackedFrontier;
#: snapshot-enabled so a warm-restarted service answers its retained
#: questions without even the resplice (values are stripped of their
#: live-only ``__dict__`` memos at capture time)
_frontier_cache = _DictCache(maxsize=16, name="frontier", snapshot=True)
#: whole packed sweeps — (chains, points) -> PackedSweep; snapshot-enabled
#: (capture strips the device-resident ``_f32`` stack)
_sweep_cache = _DictCache(maxsize=8, name="sweep", snapshot=True)


def _restore_segment(value, env):
    """Remap a snapshotted (ids, sizes, weights) segment onto the live
    model-id interning (see :func:`repro.core.memo.restore_caches`)."""
    ids, sizes, weights = value
    remap = env["model_ids"]
    ids = np.ascontiguousarray(remap[np.asarray(ids, dtype=np.int64)])
    ids.setflags(write=False)
    return (ids, sizes, weights)


def _remap_ids(ids, remap, shared: Dict[int, np.ndarray]) -> np.ndarray:
    """Remap one interned-ids array, preserving object sharing (rectangular
    sweeps alias a single ids array across all their per-point frontiers —
    ``PackedSweep.rectangular`` leans on that identity)."""
    key = id(ids)
    if key not in shared:
        out = np.ascontiguousarray(
            remap[np.asarray(ids, dtype=np.int64)].astype(np.int32))
        out.setflags(write=False)
        shared[key] = out
    return shared[key]


def _strip_frontier(f: "PackedFrontier") -> "PackedFrontier":
    """A clean copy without the cached ``_f32`` views (capture transform)."""
    return PackedFrontier(f.ids, f.sizes, f.weights, f.tile_segments,
                          f.n_segments)


def _restore_frontier(value, env, shared=None):
    f = value
    ids = _remap_ids(f.ids, env["model_ids"],
                     shared if shared is not None else {})
    return PackedFrontier(ids, f.sizes, f.weights, f.tile_segments,
                          f.n_segments)


def _strip_sweep(s: "PackedSweep") -> "PackedSweep":
    """Capture transform: drop the device-resident ``_f32`` stack and the
    ``_rect`` memo (both rebuild lazily), and canonicalize a rectangular
    sweep's equal per-point ids arrays onto ONE shared object — the
    pickle then stores a single ids array per sweep (not ``n_points``
    equal copies) and :func:`_remap_ids`' sharing-preserving restore
    keeps the alias, so ``rectangular`` short-circuits on identity."""
    frontiers = tuple(_strip_frontier(f) for f in s.frontiers)
    if frontiers and s.rectangular:
        ids0 = frontiers[0].ids
        frontiers = frontiers[:1] + tuple(
            PackedFrontier(ids0, f.sizes, f.weights, f.tile_segments,
                           f.n_segments) for f in frontiers[1:])
    return PackedSweep(s.points, s.n_designs, frontiers)


def _restore_sweep(value, env):
    shared: Dict[int, np.ndarray] = {}
    frontiers = tuple(_restore_frontier(f, env, shared)
                      for f in value.frontiers)
    return PackedSweep(value.points, value.n_designs, frontiers)


memo_module.register_restore_transform("packed_spec", _restore_segment)
memo_module.register_capture_transform("frontier", _strip_frontier)
memo_module.register_restore_transform("frontier", _restore_frontier)
memo_module.register_capture_transform("sweep", _strip_sweep)
memo_module.register_restore_transform("sweep", _restore_sweep)

#: caches owned by other modules (e.g. autocomplete's frontier
#: enumeration memo) that must drain with ours: name -> (info_fn, clear_fn)
_EXTERNAL_CACHES: Dict[str, Tuple[Callable, Callable]] = {}


def register_cache(name: str, info_fn: Callable[[], Tuple],
                   clear_fn: Callable[[], None]) -> None:
    """Hook an external memo into :func:`clear_caches`/:func:`cache_info`
    (keeps 'clear everything' a single call as the cache stack grows)."""
    _EXTERNAL_CACHES[name] = (info_fn, clear_fn)


def clear_caches() -> None:
    # MEMO_LOCK makes the drain atomic with respect to concurrent scorers:
    # no thread can repopulate one layer while a later layer is still being
    # cleared (every DictCache put/get takes the same re-entrant lock).
    with MEMO_LOCK:
        _compiled_operation.cache_clear()
        _segment_cache.clear()
        _frontier_cache.clear()
        _sweep_cache.clear()
        templatecost.clear_template_caches()
        clear_synthesis_caches()
        for _, clear_fn in _EXTERNAL_CACHES.values():
            clear_fn()


def cache_info() -> Dict[str, Tuple]:
    from repro.core.synthesis import (_instantiate_levels,
                                      _zipf_collision_mass,
                                      symbolic_breakdown)
    with MEMO_LOCK:
        info = {"compiled_operation": _compiled_operation.cache_info(),
                "packed_spec": _segment_cache.info(),
                "frontier": _frontier_cache.info(),
                "sweep": _sweep_cache.info(),
                "instantiate": _instantiate_levels.cache_info(),
                "zipf_mass": _zipf_collision_mass.cache_info(),
                "symbolic_breakdown": symbolic_breakdown.cache_info()}
        info.update(templatecost.cache_info())
        for name, (info_fn, _) in _EXTERNAL_CACHES.items():
            info[name] = info_fn()
        return info


# ---------------------------------------------------------------------------
# Frontier packing (hardware-independent)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PackedFrontier:
    """A whole frontier flattened to parallel record arrays.

    Hardware never enters the packing — score the same object against any
    number of profiles (``score(hw)``); with the fused engine that is a
    pure device parameter-table swap.
    """

    ids: np.ndarray            # int32   [R] — interned Level-2 model ids
    sizes: np.ndarray          # float64 [R] — primitive size arguments
    weights: np.ndarray        # float64 [R] — count x op-mix weight
    #: design index per TILE-record tile, sorted ascending; each design's
    #: record block is padded to a TILE multiple (pad rows carry weight 0)
    tile_segments: np.ndarray  # int64 [R // TILE]
    n_segments: int

    @property
    def segments(self) -> np.ndarray:
        """Per-record design indices (expanded from the tile layout)."""
        return np.repeat(self.tile_segments, devicecost.TILE)

    def _fused_arrays(self) -> Tuple[np.ndarray, ...]:
        """Device-dtype views for the fused scorer, converted once.

        Steady-state what-if serving scores the same retained frontier
        over and over; caching the float32/int32 conversions here (the
        instance is frozen — the memo rides its ``__dict__``) keeps each
        repeat score a pure device call instead of three array copies.
        """
        cached = self.__dict__.get("_f32")
        if cached is None:
            cached = (np.asarray(self.ids, np.int32),
                      np.asarray(self.sizes, np.float32),
                      np.asarray(self.weights, np.float32),
                      np.asarray(self.tile_segments, np.int32))
            object.__setattr__(self, "_f32", cached)
        return cached

    def split(self, n_parts: int) -> List["PackedFrontier"]:
        """Segment-contiguous sub-frontiers on tile-aligned cuts (via
        :func:`repro.core.templatecost.segment_ranges`) — the serving
        shard pool's partition primitive.  Concatenating the parts'
        ``score`` outputs reproduces ``score`` on the whole frontier
        bit for bit: every design's records land wholly in one part."""
        n_parts = max(min(n_parts, self.n_segments), 1)
        if n_parts <= 1:
            return [self]
        seg_cuts, tile_cuts = templatecost.segment_ranges(
            self.tile_segments, self.n_segments, n_parts)
        tile = devicecost.TILE
        return [PackedFrontier(
            self.ids[tile_cuts[d] * tile:tile_cuts[d + 1] * tile],
            self.sizes[tile_cuts[d] * tile:tile_cuts[d + 1] * tile],
            self.weights[tile_cuts[d] * tile:tile_cuts[d + 1] * tile],
            self.tile_segments[tile_cuts[d]:tile_cuts[d + 1]]
            - seg_cuts[d],
            int(seg_cuts[d + 1] - seg_cuts[d]))
            for d in range(n_parts)]

    def score(self, hw: HardwareProfile, engine: str = "fused",
              device=None) -> np.ndarray:
        """Per-design totals under ``hw`` via the selected engine.
        ``device`` passes through to
        :func:`repro.core.devicecost.score_frontier` (fused only)."""
        if engine == "fused":
            ids, sizes, weights, tiles = self._fused_arrays()
            return devicecost.score_frontier(
                ids, sizes, weights, tiles,
                self.n_segments, hw, device=device)
        if engine != "grouped":
            raise ValueError(f"unknown engine: {engine!r}")
        segments = self.segments
        totals = np.zeros(self.n_segments, dtype=np.float64)
        for mid in np.unique(self.ids):
            mask = self.ids == mid
            y = _predict_padded(hw.model(_MODEL_NAMES[mid]),
                                self.sizes[mask])
            contrib = self.weights[mask] * y
            totals += np.bincount(segments[mask], weights=contrib,
                                  minlength=self.n_segments)
        return totals


def _assemble_frontier(per_spec: List[Tuple[np.ndarray, ...]]
                       ) -> PackedFrontier:
    if not per_spec:
        empty = np.zeros(0)
        return PackedFrontier(empty.astype(np.int32), empty, empty,
                              empty.astype(np.int64), 0)
    tile_segments = np.repeat(
        np.arange(len(per_spec), dtype=np.int64),
        [len(ids) // devicecost.TILE for ids, _, _ in per_spec])
    return PackedFrontier(
        np.concatenate([p[0] for p in per_spec]),
        np.concatenate([p[1] for p in per_spec]),
        np.concatenate([p[2] for p in per_spec]),
        tile_segments, len(per_spec))


def pack_frontier(specs: Sequence[DataStructureSpec], workload: Workload,
                  mix: Optional[Dict[str, float]] = None) -> PackedFrontier:
    """Flatten a frontier into parallel record arrays (no hardware).

    Incremental by construction: per-spec segments live in the segment
    cache keyed on the chain hash, so only never-seen chains reach the
    template-vectorized synthesizer (:func:`templatecost.pack_specs` —
    batched numpy ops, no per-design Python); everything else splices its
    retained segment back in.  A frontier packed with identical (chains,
    workload, mix) is returned whole from the frontier cache — the
    steady-state what-if-serving path.
    """
    mix = mix or {"get": float(workload.n_queries)}
    mix_items = tuple(mix.items())
    if not specs:
        return _assemble_frontier([])
    chains = tuple(spec.chain for spec in specs)
    frontier_key = (chains, workload, mix_items)
    packed = _frontier_cache.get(frontier_key)
    if packed is not None:
        return packed
    per_spec: List[Optional[Tuple[np.ndarray, ...]]] = []
    missing: Dict[Tuple[Element, ...], List[int]] = {}
    for i, chain in enumerate(chains):
        seg = _segment_cache.get((chain, workload, mix_items))
        per_spec.append(seg)
        if seg is None:
            missing.setdefault(chain, []).append(i)
    if missing:
        new_chains = list(missing)
        for chain, seg in zip(new_chains, templatecost.pack_specs(
                new_chains, workload, mix_items)):
            _segment_cache.put((chain, workload, mix_items), seg)
            for i in missing[chain]:
                per_spec[i] = seg
    packed = _assemble_frontier(per_spec)
    _frontier_cache.put(frontier_key, packed)
    return packed


def concat_frontiers(parts: Sequence[PackedFrontier]) -> PackedFrontier:
    """Splice packed frontiers into one (designs keep their order).

    The composition primitive behind incremental search: hill-climb/beam
    rounds pack only newly-mutated designs and splice them onto retained
    frontiers, and ``whatif.what_if_design`` scores baseline+variant as
    one spliced two-design frontier.  Scoring the result is identical to
    packing the concatenated spec list from scratch — segments are
    reused byte-for-byte, only the design numbering shifts.
    """
    parts = [p for p in parts if p.n_segments]
    if not parts:
        return _assemble_frontier([])
    if len(parts) == 1:
        return parts[0]
    offsets = np.cumsum([0] + [p.n_segments for p in parts[:-1]])
    return PackedFrontier(
        np.concatenate([p.ids for p in parts]),
        np.concatenate([p.sizes for p in parts]),
        np.concatenate([p.weights for p in parts]),
        np.concatenate([p.tile_segments + off
                        for p, off in zip(parts, offsets)]),
        sum(p.n_segments for p in parts))


# ---------------------------------------------------------------------------
# Workload sweeps: (designs x workloads) grids as one scoring product
# ---------------------------------------------------------------------------
#: one sweep point: (workload, frozen mix items)
SweepPoint = Tuple[Workload, Tuple[Tuple[str, float], ...]]


def normalize_points(workloads: Sequence[Workload],
                     mixes=None) -> Tuple[SweepPoint, ...]:
    """Canonical (workload, mix_items) points of a sweep.

    ``mixes`` may be ``None`` (each workload's default get-only mix), one
    mix dict applied to every point, or a sequence of per-point mix
    dicts (a read/write-ratio sweep varies the mix, not the workload).
    """
    workloads = tuple(workloads)
    if not workloads:
        raise ValueError("a sweep needs at least one workload point")
    if mixes is None or isinstance(mixes, dict):
        mixes = [mixes] * len(workloads)
    else:
        mixes = list(mixes)
        if len(mixes) != len(workloads):
            raise ValueError(f"{len(mixes)} mixes for "
                             f"{len(workloads)} workloads")
    return tuple(
        (w, tuple((mix or {"get": float(w.n_queries)}).items()))
        for w, mix in zip(workloads, mixes))


@dataclasses.dataclass(frozen=True)
class PackedSweep:
    """A (designs x workloads) grid packed for fused scoring.

    One :class:`PackedFrontier` per sweep point over the same designs.
    When the grid is *rectangular* — every point shares the record layout
    (same template statics; the common case for read/write-ratio, skew,
    selectivity or query-count sweeps at a fixed data size) — the frozen
    per-point frontiers share one ids array (``pack_sweep`` packs them as
    one grid), and ``score``
    issues ONE :func:`repro.core.devicecost.score_sweep` call whose bank
    gathers are amortized across every workload.  Non-rectangular sweeps
    (``n_entries`` changing a chain's level structure) degrade gracefully
    to one spliced flat fused call.

    Hardware never enters the packing: scoring the same sweep against
    another profile is a pure parameter-table swap (zero recompilation,
    asserted in ``tests/test_sweep.py``).
    """

    points: Tuple[SweepPoint, ...]
    n_designs: int
    frontiers: Tuple[PackedFrontier, ...]   # one per point

    @property
    def n_points(self) -> int:
        return len(self.points)

    @property
    def rectangular(self) -> bool:
        cached = self.__dict__.get("_rect")
        if cached is None:
            f0 = self.frontiers[0] if self.frontiers else None
            cached = all(
                f.ids is f0.ids or np.array_equal(f.ids, f0.ids)
                for f in self.frontiers[1:])
            object.__setattr__(self, "_rect", cached)
        return cached

    def _sweep_arrays(self) -> Tuple:
        """(host ids, device-committed arrays), built once per sweep.

        Steady-state serving re-scores the same retained sweep; caching
        the padded float32 stack — resident on device when it fits one
        fused chunk (:func:`repro.core.devicecost.to_device_sweep`) —
        makes each repeat score a pure fused dispatch: no padding, no
        dtype conversion, no copies in either direction (the host-side
        ids stay cached for the scorer's availability check).
        """
        cached = self.__dict__.get("_f32")
        if cached is None:
            f0 = self.frontiers[0]
            bucket = devicecost._pow2(len(f0.ids), 16)
            padded = devicecost.pad_sweep(
                np.asarray(f0.ids, np.int32),
                np.stack([f.sizes for f in self.frontiers]),
                np.stack([f.weights for f in self.frontiers]),
                np.asarray(f0.tile_segments, np.int32), bucket)
            cached = (padded[0], devicecost.to_device_sweep(*padded))
            object.__setattr__(self, "_f32", cached)
        return cached

    def split(self, n_parts: int) -> List["PackedSweep"]:
        """Design-contiguous sub-sweeps (the serving shard pool's
        partition primitive): every point's frontier splits on the same
        design cuts, so stacking the parts' grids along axis 1
        reproduces ``score`` bit for bit.  Rectangular sweeps stay
        rectangular — each cut's ids slice is shared across points by
        object identity, exactly like the parent's interned ids."""
        n_parts = max(min(n_parts, self.n_designs), 1)
        if n_parts <= 1:
            return [self]
        shared_ids: Dict[int, List[np.ndarray]] = {}
        per_point: List[List[PackedFrontier]] = []
        for f in self.frontiers:
            parts = f.split(n_parts)
            cached = shared_ids.get(id(f.ids))
            if cached is None:
                shared_ids[id(f.ids)] = [p.ids for p in parts]
            else:
                parts = [PackedFrontier(cached[d], p.sizes, p.weights,
                                        p.tile_segments, p.n_segments)
                         for d, p in enumerate(parts)]
            per_point.append(parts)
        return [PackedSweep(self.points, per_point[0][d].n_segments,
                            tuple(row[d] for row in per_point))
                for d in range(n_parts)]

    def score(self, hw: HardwareProfile, engine: str = "fused",
              device=None) -> np.ndarray:
        """The ``[n_points, n_designs]`` totals grid under ``hw``.

        ``engine="grouped"`` scores each point's frontier through the
        PR-1 grouped oracle — bit-identical to looping ``cost_many(...,
        engine="grouped")`` per workload.  ``device`` routes the fused
        call onto one specific device.
        """
        if self.n_designs == 0 or not self.points:
            return np.zeros((self.n_points, self.n_designs))
        if engine == "fused":
            if self.rectangular:
                host_ids, (ids, sizes, weights, tiles) = \
                    self._sweep_arrays()
                return devicecost.score_sweep(ids, sizes, weights, tiles,
                                              self.n_designs, hw,
                                              host_ids=host_ids,
                                              device=device)
            # non-rectangular: one spliced flat fused call over the
            # whole grid (point-major), not one dispatch per point
            flat = concat_frontiers(list(self.frontiers))
            return flat.score(hw, device=device).reshape(
                self.n_points, self.n_designs)
        if engine != "grouped":
            raise ValueError(f"unknown engine: {engine!r}")
        return np.stack([f.score(hw, engine=engine)
                         for f in self.frontiers])


def pack_sweep(specs: Sequence[DataStructureSpec],
               workloads: Sequence[Workload],
               mixes=None) -> PackedSweep:
    """Pack a (designs x workloads) grid, splicing shared template
    statics with per-workload geometry columns.

    The grid path: the points are grouped by (op sequence, depth
    signature) and each group is packed whole over the designs in the
    caller's order (:func:`repro.core.templatecost.pack_grid` — statics
    and record layout computed once per group, numerics batched over the
    workload axis).  Each point's frontier takes its sizes and weights
    as read-only row views of its group's buffers, and every point of a
    group shares one ids and one tile-segments array, so ``rectangular``
    holds by identity.  No per-(design, point) segment is cut, looked up
    or cached: a sweep brings fresh designs, which a per-chain cache
    would only churn (``pack_frontier`` keeps that cache for the few
    chains what-if questions re-ask).  Each point's frontier still lands
    in the frontier cache, so a later single-workload ``cost_many``
    against any sweep point is a pure cache hit.  A repeated identical
    sweep is one sweep-cache hit.
    """
    points = normalize_points(workloads, mixes)
    specs = list(specs)
    chains = tuple(spec.chain for spec in specs)
    sweep_key = (chains, points)
    cached = _sweep_cache.get(sweep_key)
    if cached is not None:
        return cached
    if not chains:
        frontiers = [_assemble_frontier([])] * len(points)
    else:
        frontiers = [None] * len(points)
        for grid in templatecost.pack_grid(chains, points):
            for row, pi in enumerate(grid.points):
                frontiers[pi] = PackedFrontier(
                    grid.ids, grid.sizes[row], grid.weights[row],
                    grid.tile_segments, len(chains))
        spans.add_grid_cells(len(chains) * len(points))
        for (workload, mix_items), frontier in zip(points, frontiers):
            _frontier_cache.put((chains, workload, mix_items), frontier)
    sweep = PackedSweep(points, len(specs), tuple(frontiers))
    _sweep_cache.put(sweep_key, sweep)
    return sweep


def concat_sweeps(parts: Sequence["PackedSweep"]) -> PackedSweep:
    """Splice sweeps over the SAME points along the design axis.

    The serving coalescing primitive: concurrent sweep requests sharing
    a workload-point axis combine into one grid and one fused call, like
    PR-4's ``concat_frontiers`` window batching for flat questions.
    """
    parts = list(parts)
    if not parts:
        raise ValueError("concat_sweeps needs at least one sweep")
    points = parts[0].points
    for p in parts[1:]:
        if p.points != points:
            raise ValueError("cannot splice sweeps over different "
                             "workload points")
    if len(parts) == 1:
        return parts[0]
    frontiers = tuple(
        concat_frontiers([p.frontiers[w] for p in parts])
        for w in range(len(points)))
    return PackedSweep(points, sum(p.n_designs for p in parts), frontiers)


def cost_sweep(specs: Sequence[DataStructureSpec],
               workloads: Sequence[Workload], hw: HardwareProfile,
               mixes=None, engine: str = "fused") -> np.ndarray:
    """Workload cost for every (workload, design) cell, as one grid.

    Equivalent to stacking ``cost_many(specs, w, hw, mix)`` per sweep
    point (grouped engine: bit-identical; fused: one
    :func:`~repro.core.devicecost.score_sweep` call whose totals match
    the scalar oracle to the documented 1e-6).  Returns shape
    ``[len(workloads), len(specs)]``.
    """
    return pack_sweep(specs, workloads, mixes).score(hw, engine=engine)


# ---------------------------------------------------------------------------
# Frontier evaluation
# ---------------------------------------------------------------------------
def cost_many(specs: Sequence[DataStructureSpec], workload: Workload,
              hw: HardwareProfile,
              mix: Optional[Dict[str, float]] = None,
              engine: str = "fused") -> np.ndarray:
    """Workload cost for every spec in one batched evaluation.

    Equivalent to ``[cost_workload(s, workload, hw, mix) for s in specs]``.
    The default fused engine scores the packed frontier in one jitted JAX
    call (totals within 1e-6 relative of the scalar path — float32 banked
    evaluation, see :mod:`repro.core.devicecost`); ``engine="grouped"``
    keeps the PR-1 per-model grouped oracle, whose per-record predictions
    are bit-identical to the scalar path (same model code, same float32
    inputs) so totals agree to float64 accumulation tolerance (~1e-12
    relative) and argmins coincide exactly.
    """
    return pack_frontier(specs, workload, mix).score(hw, engine=engine)


def cost_one(op: str, spec: DataStructureSpec, workload: Workload,
             hw: HardwareProfile) -> float:
    """Batched-path cost of a single operation (compiled + memoized)."""
    return compiled_operation(op, spec, workload).total(hw)


def cost_workload_batched(spec: DataStructureSpec, workload: Workload,
                          hw: HardwareProfile,
                          mix: Optional[Dict[str, float]] = None,
                          engine: str = "fused") -> float:
    """Drop-in batched equivalent of :func:`repro.core.synthesis.cost_workload`."""
    return float(cost_many([spec], workload, hw, mix, engine=engine)[0])
