"""Fused device-resident frontier scoring: one jitted call per frontier.

PR 1's grouped engine (:mod:`repro.core.batchcost`) already evaluates a
whole candidate frontier with one vectorized ``FittedModel.predict`` per
Level-2 model — but that is still a Python loop over ~14 models with a
host<->device round trip each.  This module removes the loop: an entire
:class:`~repro.core.hardware.HardwareProfile` is packed once into
device-resident *parameter banks*, and a frontier — parallel
``(model_id, size, weight, segment)`` arrays — is scored by a single
jitted function that

1. gathers each record's parameters from per-kind stacked banks
   (kind-masked, so every record evaluates all three families and selects
   the right one — branch-free and fully vectorized);
2. reduces records to per-design totals with a dense ``TILE``-wide
   pre-reduction followed by one ``segment_sum``.

Banks cover the whole model zoo:

* the **linear-basis family** (linear / log_linear / log_loglog / nlogn)
  collapses into one canonical 4-feature basis ``[x, ln x, ln ln x,
  x ln x]`` with per-model weight rows (absent features carry weight 0);
* **sigmoids** (and **sigmoids2d**, whose plain-predict is its m=1 slice
  S1) stack into ``[M, K]`` amplitude/slope/center banks, zero-padded;
* **knn** joins via a fixed k=4 ``top_k`` over inverse log-distance
  weights with sentinel-masked padding (see ``models._knn_predict``).

Shapes are bucketed exactly like ``batchcost._predict_padded`` — records
and segment counts pad to powers of two (chunked at ``_MAX_FUSED_RECORDS``)
— so XLA compiles a bounded shape set.  Bank widths are fixed per process,
which makes a what-if-hardware question a pure parameter-table swap: a new
profile builds new banks of identical shape and reuses the compiled
executable with **zero recompilation** (asserted via :func:`trace_count`).
Several devices are the serving tier's concern:
:class:`repro.serving.shards.ScoringShardPool` splits a product on design
boundaries and routes each part here with ``device=``.

Totals agree with the grouped PR-1 oracle to <=1e-6 relative (XLA fuses
the banked computation differently than the per-kind eager predicts, and
the segment reduction runs in float32) — relaxed from the 1e-9
scalar/grouped contract, see ``tests/test_batchcost.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import memo, spans
from repro.core.hardware import HardwareProfile
from repro.core.memo import MEMO_LOCK
from repro.core.models import _BASES, KNN_SENTINEL
from repro.testing import faults

# ---------------------------------------------------------------------------
# Level-2 model-name interning: frontier records refer to models by id.
# Owned here (the table rows are aligned to it); batchcost re-exports.
# Guarded by the shared memo lock: a torn read of (_MODEL_IDS,
# _MODEL_NAMES) under concurrent serving threads could hand two models
# one id, silently mis-scoring every frontier that uses either.
# ---------------------------------------------------------------------------
_MODEL_IDS: Dict[str, int] = {}
_MODEL_NAMES: List[str] = []


def model_id(name: str) -> int:
    mid = _MODEL_IDS.get(name)
    if mid is None:
        with MEMO_LOCK:
            mid = _MODEL_IDS.get(name)
            if mid is None:
                mid = len(_MODEL_NAMES)
                _MODEL_NAMES.append(name)
                _MODEL_IDS[name] = mid
    return mid


def _capture_model_names() -> List[str]:
    with MEMO_LOCK:
        return list(_MODEL_NAMES)


def _restore_model_remap(names: List[str]) -> np.ndarray:
    """old interned id -> live id, re-interning every snapshotted name.

    Ids are assigned lazily in first-use order, so a restarted process
    (or one that interned extra names first) may disagree with the
    snapshot; every id-bearing restored value is rewritten through this
    remap (a fresh process re-interns in snapshot order, making the
    remap the identity)."""
    return np.asarray([model_id(n) for n in names], dtype=np.int32)


memo.register_snapshot_env("model_ids", _capture_model_names,
                           _restore_model_remap)


def model_name(mid: int) -> str:
    return _MODEL_NAMES[mid]


KIND_LINEAR, KIND_SIGMOID, KIND_KNN = 0, 1, 2

#: canonical feature positions of each basis' weight vector, in order —
#: e.g. nlogn's basis is [x ln x, x], landing at canonical slots (3, 0)
_CANONICAL_SLOTS = {
    "linear": (0,),
    "log_linear": (0, 1),
    "log_loglog": (0, 1, 2),
    "nlogn": (3, 0),
}

#: fixed per-process bank widths; profiles needing more grow to the next
#: power of two (a width change recompiles once, then stays fixed)
_SIG_SLOTS = 4
_KNN_SLOTS = 16

#: largest fused record-chunk; bigger frontiers accumulate over chunks
_MAX_FUSED_RECORDS = 1 << 18

#: records per reduction tile: packing pads every design's record block to
#: a multiple of TILE (pad rows carry weight 0), so an in-register dense
#: reshape-sum shrinks the scatter by 8x before the single segment_sum —
#: XLA's scatter-add is serial on CPU and the frontier reduction would
#: otherwise dominate the fused call
TILE = 8


def _pow2(n: int, floor: int) -> int:
    return max(1 << max(n - 1, 0).bit_length(), floor)


@dataclasses.dataclass(frozen=True)
class DeviceTable:
    """One profile's parameter banks, resident on device.

    ``banks`` is the jit-traced pytree; the remaining fields are host-side
    metadata (row validity, interning watermark) used to validate frontiers
    and to decide when a table must be rebuilt.
    """

    profile_name: str
    banks: Dict[str, jax.Array]   # kinds/lin_*/sig_*/knn_*/xlo/xhi, [M,...]
    avail: np.ndarray             # bool [M] — rows backed by a fitted model
    n_interned: int               # len(_MODEL_NAMES) at build time
    sig_slots: int
    knn_slots: int
    has_knn: bool                 # static jit flag: skip top_k when False
    models_ref: int               # id() of the models dict banked here

    @property
    def n_rows(self) -> int:
        return int(self.banks["kinds"].shape[0])


def build_table(hw: HardwareProfile, *, sig_slots: int = _SIG_SLOTS,
                knn_slots: int = _KNN_SLOTS) -> DeviceTable:
    """Pack every fitted model of ``hw`` into stacked device banks."""
    for name in hw.models:
        model_id(name)          # rows must exist for every profile model
    needed_sig = max([sig_slots] + [
        len(np.atleast_1d(m.params[key]))
        for m in hw.models.values() for key in ("c", "s1_c")
        if key in m.params])
    needed_knn = max([knn_slots] + [
        len(np.atleast_1d(m.params["x"]))
        for m in hw.models.values() if m.kind == "knn"])
    sig_slots = _pow2(needed_sig, sig_slots)
    knn_slots = _pow2(needed_knn, knn_slots)

    m_rows = _pow2(len(_MODEL_NAMES), 16)
    kinds = np.zeros(m_rows, np.int32)
    lin_w = np.zeros((m_rows, 4), np.float32)
    lin_y0 = np.zeros(m_rows, np.float32)
    sig_c = np.zeros((m_rows, sig_slots), np.float32)
    sig_k = np.ones((m_rows, sig_slots), np.float32)
    sig_x0 = np.zeros((m_rows, sig_slots), np.float32)
    sig_y0 = np.zeros(m_rows, np.float32)
    knn_lx = np.full((m_rows, knn_slots), KNN_SENTINEL, np.float32)
    knn_y = np.zeros((m_rows, knn_slots), np.float32)
    xlo = np.ones(m_rows, np.float32)
    xhi = np.ones(m_rows, np.float32)
    avail = np.zeros(m_rows, bool)

    for name, model in hw.models.items():
        row = _MODEL_IDS[name]
        avail[row] = True
        xlo[row], xhi[row] = model.x_range
        p = model.params
        if model.kind in _BASES:
            for w_val, slot in zip(np.atleast_1d(p["w"]),
                                   _CANONICAL_SLOTS[model.kind]):
                lin_w[row, slot] = w_val
            lin_y0[row] = p["y0"]
        elif model.kind in ("sigmoids", "sigmoids2d"):
            prefix = "s1_" if model.kind == "sigmoids2d" else ""
            kinds[row] = KIND_SIGMOID
            n_sig = len(np.atleast_1d(p[prefix + "c"]))
            sig_c[row, :n_sig] = p[prefix + "c"]
            sig_k[row, :n_sig] = p[prefix + "k"]
            sig_x0[row, :n_sig] = p[prefix + "x0"]
            sig_y0[row] = p[prefix + "y0"]
        elif model.kind == "knn":
            kinds[row] = KIND_KNN
            n_pts = len(p["x"])
            knn_lx[row, :n_pts] = np.log(
                np.asarray(p["x"], np.float32) + 1.0)
            knn_y[row, :n_pts] = p["y"]
        else:
            raise ValueError(f"unbankable model kind: {model.kind}")

    banks = {k: jnp.asarray(v) for k, v in {
        "kinds": kinds, "lin_w": lin_w, "lin_y0": lin_y0,
        "sig_c": sig_c, "sig_k": sig_k, "sig_x0": sig_x0, "sig_y0": sig_y0,
        "knn_lx": knn_lx, "knn_y": knn_y, "xlo": xlo, "xhi": xhi}.items()}
    # chaos seam: a corrupt rule NaN-poisons the float banks (the int
    # gather indices stay intact), surfacing as non-finite fused totals
    # until invalidate_table() forces a clean rebuild
    banks = faults.corrupt("devicecost.banks", banks, key=hw.name)
    return DeviceTable(hw.name, banks, avail, len(_MODEL_NAMES),
                       sig_slots, knn_slots,
                       has_knn=bool((kinds[avail] == KIND_KNN).any()),
                       models_ref=id(hw.models))


def device_table(hw: HardwareProfile) -> DeviceTable:
    """The (cached) device table of a profile, rebuilt when stale.

    A table goes stale when the global model-name interning has grown past
    its watermark, or when the profile's models dict is no longer the one
    that was banked (a profile derived from another must never score with
    its parent's banks); bank *shapes* stay fixed until a power-of-two
    boundary crosses, so rebuilds almost never recompile the scorer — and
    two profiles of the same model zoo always share compiled executables.
    """
    def _current(table) -> bool:
        return table is not None and \
            table.n_interned == len(_MODEL_NAMES) and \
            table.models_ref == id(hw.models)

    with MEMO_LOCK:   # consistent staleness check vs concurrent interning
        table = hw._device_table
        if _current(table):
            return table
    # build OUTSIDE the lock — bank construction is the expensive path and
    # must not stall every concurrent scorer's cache traffic; two racing
    # threads may build duplicate (equal) tables, last write wins
    table = build_table(hw)
    with MEMO_LOCK:
        stale = hw._device_table
        hw._device_table = table
        if stale is not None:
            _BANK_REPLICAS.discard(lambda k, v: v[0] is stale)
        return table


def invalidate_table(hw: HardwareProfile) -> None:
    """Drop a profile's cached device table and every bank replica of it.

    The serving tier's degraded-engine recovery probe calls this before
    re-trying the fused engine: if the banks were corrupted (non-finite
    totals demoted the profile to the grouped oracle), the next
    :func:`device_table` call rebuilds them from the fitted models."""
    with MEMO_LOCK:
        stale = hw._device_table
        hw._device_table = None
        if stale is not None:
            _BANK_REPLICAS.discard(lambda k, v: v[0] is stale)


# ---------------------------------------------------------------------------
# Per-device bank placement.  A table's banks live wherever jax put them
# (device 0); the serving shard pool needs them committed to the one
# SPECIFIC device each part runs on.  That placement happens once per
# (table, device) and is interned in the ``device_banks`` cache — after
# that, repeat scores touch the host only for the O(R) availability
# check.  Keys carry ``id(table)``; the value keeps a strong reference to
# the table, so the id cannot be reused while its entry lives, and
# ``device_table`` discards a profile's replicas the moment it swaps in a
# rebuilt table.
# ---------------------------------------------------------------------------
_BANK_REPLICAS = memo.DictCache(maxsize=32, name="device_banks")


def _banks_on(table: DeviceTable, device) -> Dict[str, jax.Array]:
    """The table's banks committed to one specific local device (the
    serving shard pool routes each partition's jit dispatch by device)."""
    key = (id(table), "device", device.id)
    hit = _BANK_REPLICAS.get(key)
    if hit is not None and hit[0] is table:
        return hit[1]
    banks = jax.device_put(table.banks, device)
    _BANK_REPLICAS.put(key, (table, banks))
    return banks


# ---------------------------------------------------------------------------
# The fused scorer
# ---------------------------------------------------------------------------
#: traced-function entry counter — increments only while jax (re)traces the
#: kernel, i.e. exactly once per compiled (shape, static-arg) signature.
#: Tests probe it to assert what-if-hardware swaps trigger no recompilation.
_TRACE_COUNT = [0]


def trace_count() -> int:
    return _TRACE_COUNT[0]


def bank_predict(banks: Dict[str, jax.Array], ids: jax.Array,
                 x: jax.Array, with_knn: bool) -> jax.Array:
    """Per-record model evaluation against stacked parameter banks.

    ``ids`` is ``[R]``; ``x`` is ``[..., R]`` — any number of leading
    batch axes (the flat scorer passes ``[R]``, the sweep scorer
    ``[W, R]``) broadcast against the ``[R, ...]`` bank gathers via the
    trailing record dimension, so both kernels share one body and the
    parameter gathers are issued once per record regardless of the
    batch shape.  Differentiable in ``x`` through the linear-basis and
    sigmoid families (``jnp.clip``/``log``/``sigmoid`` are smooth
    inside the fitted range), which is what lets
    :mod:`repro.core.relax` drive ``jax.grad`` through the very same
    bank rows the fused engine scores with.  knn rows join through a
    ``top_k`` gather whose value-gradients flow through the inverse
    log-distance weights.

    The short per-record sums (basis features, sigmoid slots, knn
    neighbours) are written as explicit left-to-right adds over bank
    columns, never as a ``sum`` over a trailing axis: a TPU orders such
    a reduction by the array's layout, which follows the batch shape, so
    a ``[2, R]`` chunk and an ``[8, R]`` chunk would round the same
    record differently.  Written out, every record's value is the same
    whatever the shape, which is what keeps pooled, chunked and flat
    scores equal bit for bit.
    """
    def col(name: str, j: int) -> jax.Array:
        return banks[name][:, j][ids]

    x = jnp.clip(x, banks["xlo"][ids], banks["xhi"][ids])
    lx = jnp.log(x + 1.0)

    feats = (x, lx, jnp.log(lx + 1.0), x * lx)
    lin = _ordered_sum([f * col("lin_w", j) for j, f in enumerate(feats)]
                       ) + banks["lin_y0"][ids]

    sig = _ordered_sum([
        jax.nn.sigmoid(col("sig_k", j) * (lx - col("sig_x0", j)))
        * col("sig_c", j) for j in range(banks["sig_k"].shape[1])]
    ) + banks["sig_y0"][ids]

    kind = banks["kinds"][ids]
    y = jnp.where(kind == KIND_SIGMOID, sig, lin)
    if with_knn:   # static: profiles without knn models skip the top_k
        klx = banks["knn_lx"][ids]
        d = jnp.abs(lx[..., None] - klx) + 1e-6
        w = jnp.where(klx >= KNN_SENTINEL * 0.5, 0.0, 1.0 / d)
        wk, idx = jax.lax.top_k(w, 4)
        yk = jnp.take_along_axis(
            jnp.broadcast_to(banks["knn_y"][ids], w.shape), idx, axis=-1)
        knn = _ordered_sum([wk[..., j] * yk[..., j] for j in range(4)]) / \
            jnp.maximum(_ordered_sum([wk[..., j] for j in range(4)]), 1e-30)
        y = jnp.where(kind == KIND_KNN, knn, y)
    return jnp.maximum(y, 0.0)


def _ordered_sum(terms: List[jax.Array]) -> jax.Array:
    """``terms[0] + terms[1] + ...`` in that order (see
    :func:`bank_predict`: an explicit chain of adds rounds the same on
    every array shape)."""
    return functools.reduce(operator.add, terms)


def _score_kernel(banks: Dict[str, jax.Array], ids: jax.Array,
                  sizes: jax.Array, weights: jax.Array,
                  segments: jax.Array, n_segments: int,
                  with_knn: bool) -> jax.Array:
    _TRACE_COUNT[0] += 1
    y = bank_predict(banks, ids, sizes, with_knn)
    # tile-aligned design blocks: dense pre-reduction, then one scatter
    tiles = (weights * y).reshape(-1, TILE).sum(-1)
    return jax.ops.segment_sum(tiles, segments, num_segments=n_segments,
                               indices_are_sorted=True)


_score_jit = jax.jit(_score_kernel, static_argnums=(5, 6))


def _sweep_kernel(banks: Dict[str, jax.Array], ids: jax.Array,
                  sizes: jax.Array, weights: jax.Array,
                  segments: jax.Array, n_segments: int,
                  with_knn: bool) -> jax.Array:
    """The workload-axis twin of :func:`_score_kernel`.

    ``sizes``/``weights`` carry a leading workload axis ``[W, R]`` while
    ``ids``/``segments`` stay 1-D: a design-continuum sweep shares its
    record layout across every workload point, so the parameter-bank
    gathers (the memory-bound half of the fused call) are issued ONCE for
    all W workloads instead of once per workload — on top of collapsing W
    dispatches into one.  Per-record math is :func:`bank_predict` with a
    leading batch axis; only the reduction differs.
    """
    _TRACE_COUNT[0] += 1
    y = bank_predict(banks, ids, sizes, with_knn)
    tiles = (weights * y).reshape(y.shape[0], -1, TILE).sum(-1)
    return jax.vmap(lambda t: jax.ops.segment_sum(
        t, segments, num_segments=n_segments,
        indices_are_sorted=True))(tiles)


_sweep_jit = jax.jit(_sweep_kernel, static_argnums=(5, 6))


def _pad_records(ids: np.ndarray, sizes: np.ndarray, weights: np.ndarray,
                 tile_segments: np.ndarray, bucket: int
                 ) -> Tuple[np.ndarray, ...]:
    """Pad a tile-aligned record block to ``bucket`` rows (and its tile
    segments to ``bucket // TILE``); pad rows carry weight 0 so they
    contribute exactly nothing.  Pad segments repeat the *last* real
    segment id — appending 0 would break the sorted order that the
    kernel's ``indices_are_sorted`` scatter hint promises.

    Dtype conversions are copy-free when the input already matches —
    ``PackedFrontier`` hands the steady-state scoring path cached
    device-dtype views, so a retained frontier that lands exactly on its
    bucket reaches the jit call with zero host-side array copies."""
    n = len(ids)
    if n == bucket:
        return (np.asarray(ids, np.int32), np.asarray(sizes, np.float32),
                np.asarray(weights, np.float32),
                np.asarray(tile_segments, np.int32))
    pad = bucket - n
    seg_pad = bucket // TILE - len(tile_segments)
    seg_fill = tile_segments[-1] if len(tile_segments) else 0
    return (np.concatenate([ids, np.zeros(pad, ids.dtype)]).astype(np.int32),
            np.concatenate([sizes, np.ones(pad, sizes.dtype)]
                           ).astype(np.float32),
            np.concatenate([weights, np.zeros(pad, weights.dtype)]
                           ).astype(np.float32),
            np.concatenate([tile_segments,
                            np.full(seg_pad, seg_fill,
                                    tile_segments.dtype)]
                           ).astype(np.int32))


def _dispatch(records: int, points: int, device=None) -> spans.span:
    """The span around padding, placing and issuing one fused call
    (``device`` -1: the default device)."""
    return spans.span(spans.DISPATCH, records=records, points=points,
                      device=-1 if device is None else device.id)


def _fetch(out, records: int) -> np.ndarray:
    """One fused call's output on the host, as float64, under its span."""
    with spans.span(spans.FETCH, records=records):
        return np.asarray(out, np.float64)


def _check_frontier(table: DeviceTable, ids: np.ndarray) -> None:
    if len(ids) and not table.avail[ids].all():
        missing = sorted({_MODEL_NAMES[m] for m in np.unique(ids)
                          if not table.avail[m]})
        raise KeyError(f"profile {table.profile_name!r} has no fitted "
                       f"model for: {missing}")


def _chunk_cuts(tile_segments: np.ndarray, chunk_tiles: int) -> List[int]:
    """Tile offsets that cut a record layout into fused chunks of at most
    ``chunk_tiles`` tiles, each cut on a design boundary: every design's
    records then reduce in one ``segment_sum``, so a chunked score equals
    a one-call (or pooled) score bit for bit.  Only a design longer than
    a whole chunk is cut inside."""
    n = len(tile_segments)
    cuts = [0]
    while cuts[-1] + chunk_tiles < n:
        end = cuts[-1] + chunk_tiles
        start = int(np.searchsorted(tile_segments, tile_segments[end]))
        cuts.append(start if start > cuts[-1] else end)
    return cuts + [n]


def _refuse_shard(shard: bool) -> None:
    """The scorers take ``shard`` only as ``False``: the benchmark's
    warm-up (``bench/system.py``) still passes it, and the keyword goes
    once that file stops.  Several devices score one product through
    :class:`repro.serving.shards.ScoringShardPool`, never here."""
    if shard:
        raise ValueError("shard=True is not supported: score a product "
                         "across devices with "
                         "repro.serving.shards.ScoringShardPool")


def score_frontier(ids: np.ndarray, sizes: np.ndarray, weights: np.ndarray,
                   tile_segments: np.ndarray, n_segments: int,
                   hw: HardwareProfile, shard: bool = False,
                   device=None) -> np.ndarray:
    """Per-design totals for packed frontier records, in one fused call.

    Records must be TILE-aligned per design and ``tile_segments`` sorted
    ascending — exactly the layout
    :func:`repro.core.batchcost.pack_frontier` emits.  ``device`` routes
    the call onto one specific local device (banks committed there once,
    see :func:`_banks_on`) — the serving shard pool's dispatch
    primitive; ``None`` scores on the default device.  ``shard``: see
    :func:`_refuse_shard`.
    """
    _refuse_shard(shard)
    if n_segments == 0:
        return np.zeros(0, np.float64)
    table = device_table(hw)
    _check_frontier(table, ids)
    n_pad = _pow2(n_segments, 16)
    banks = table.banks if device is None else _banks_on(table, device)
    totals = np.zeros(n_pad, np.float64)
    cuts = _chunk_cuts(tile_segments, _MAX_FUSED_RECORDS // TILE)
    for t0, t1 in zip(cuts[:-1], cuts[1:]):
        chunk = slice(t0 * TILE, t1 * TILE)
        tile_chunk = slice(t0, t1)
        bucket = _pow2(len(ids[chunk]), 16)
        with _dispatch(bucket, 1, device):
            padded = _pad_records(ids[chunk], sizes[chunk], weights[chunk],
                                  tile_segments[tile_chunk], bucket)
            if device is not None:
                padded = tuple(jax.device_put(a, device) for a in padded)
            out = _score_jit(banks, *padded, n_pad, table.has_knn)
        totals += _fetch(out, bucket)
    return faults.corrupt("devicecost.fused", totals[:n_segments])


def pad_sweep(ids: np.ndarray, sizes: np.ndarray, weights: np.ndarray,
              tile_segments: np.ndarray, bucket: int
              ) -> Tuple[np.ndarray, ...]:
    """:func:`_pad_records` for sweep layouts: ``sizes``/``weights`` pad
    along their record axis (axis 1), ``ids``/``tile_segments`` stay 1-D.
    Public so :class:`repro.core.batchcost.PackedSweep` can cache the
    padded device-dtype arrays once and hand repeat scores a zero-copy
    call."""
    n = len(ids)
    if n == bucket:
        return (np.asarray(ids, np.int32), np.asarray(sizes, np.float32),
                np.asarray(weights, np.float32),
                np.asarray(tile_segments, np.int32))
    pad = bucket - n
    w = sizes.shape[0]
    seg_pad = bucket // TILE - len(tile_segments)
    seg_fill = tile_segments[-1] if len(tile_segments) else 0
    # pad ids repeat a REAL model id (never a blind 0): the availability
    # check may run on the padded array, and a profile without a fitted
    # model for whatever name was interned first must not spuriously
    # reject a sweep that never references it
    pad_id = ids[-1] if n else 0
    return (np.concatenate([ids, np.full(pad, pad_id, ids.dtype)]
                           ).astype(np.int32),
            np.concatenate([sizes, np.ones((w, pad), sizes.dtype)],
                           axis=1).astype(np.float32),
            np.concatenate([weights, np.zeros((w, pad), weights.dtype)],
                           axis=1).astype(np.float32),
            np.concatenate([tile_segments,
                            np.full(seg_pad, seg_fill,
                                    tile_segments.dtype)]
                           ).astype(np.int32))


def sweep_chunk(w_axis: int) -> int:
    """Largest per-chunk record count of a W-workload sweep: keeps
    W x chunk under the fused-record ceiling, cut on tile boundaries so
    no design block is ever split mid-tile."""
    return max((_MAX_FUSED_RECORDS // max(w_axis, 1)) // TILE * TILE,
               TILE)


def to_device_sweep(ids, sizes, weights, tile_segments) -> Tuple:
    """Commit padded sweep arrays to the device when they fit one fused
    chunk (the retained-sweep steady path skips every host->device copy
    on repeat scores); multi-chunk sweeps stay host-side, where the
    chunk loop slices them."""
    if len(ids) > sweep_chunk(sizes.shape[0]):
        return ids, sizes, weights, tile_segments
    with _dispatch(len(ids), int(sizes.shape[0])):
        return tuple(jnp.asarray(a)
                     for a in (ids, sizes, weights, tile_segments))


def score_sweep(ids, sizes, weights, tile_segments, n_segments: int,
                hw: HardwareProfile,
                host_ids: Optional[np.ndarray] = None,
                shard: bool = False, device=None) -> np.ndarray:
    """Per-(workload, design) totals for a rectangular sweep, one fused
    call.

    ``sizes``/``weights`` are ``[W, R]`` with a shared record layout
    (``ids`` ``[R]``, TILE-aligned per design, ``tile_segments`` sorted
    ascending — the layout :func:`repro.core.batchcost.pack_sweep`
    emits); numpy or (already padded, e.g. via :func:`to_device_sweep`)
    device arrays.  When ``ids`` is device-resident, pass ``host_ids``
    (a host-side copy) so the per-call availability check never pulls
    the array back from the device.  Returns ``[W, n_segments]``.
    Shapes are pow2-bucketed like :func:`score_frontier`, so repeat
    sweeps (and what-if-hardware swaps against a sweep) reuse the
    compiled executable with zero recompilation.  ``device`` routes the
    call onto one specific local device, as in :func:`score_frontier`;
    ``shard``: see :func:`_refuse_shard`.
    """
    _refuse_shard(shard)
    w_axis = int(sizes.shape[0])
    if n_segments == 0 or w_axis == 0:
        return np.zeros((w_axis, n_segments), np.float64)
    table = device_table(hw)
    host_ids = np.asarray(ids) if host_ids is None else host_ids
    _check_frontier(table, host_ids)
    n_pad = _pow2(n_segments, 16)
    chunk_r = sweep_chunk(w_axis)
    n = len(host_ids)
    banks = table.banks if device is None else _banks_on(table, device)
    if n == _pow2(n, 16) and n <= chunk_r:
        # bucket-aligned single chunk — the steady path: PackedSweep
        # hands over cached padded device-resident arrays plus host ids,
        # so beyond the O(R) availability check above this is a pure
        # fused dispatch with zero copies
        with _dispatch(n, w_axis, device):
            args = (ids, sizes, weights, tile_segments)
            if device is not None:
                args = tuple(jax.device_put(np.asarray(a), device)
                             for a in args)
            out = _sweep_jit(banks, *args, n_pad, table.has_knn)
        return faults.corrupt("devicecost.fused",
                              _fetch(out, n)[:, :n_segments])
    ids = host_ids
    sizes, weights = np.asarray(sizes), np.asarray(weights)
    tile_segments = np.asarray(tile_segments)
    totals = np.zeros((w_axis, n_pad), np.float64)
    cuts = _chunk_cuts(tile_segments, chunk_r // TILE)
    for t0, t1 in zip(cuts[:-1], cuts[1:]):
        chunk = slice(t0 * TILE, t1 * TILE)
        tile_chunk = slice(t0, t1)
        bucket = _pow2(len(ids[chunk]), 16)
        with _dispatch(bucket, w_axis, device):
            padded = pad_sweep(ids[chunk], sizes[:, chunk],
                               weights[:, chunk], tile_segments[tile_chunk],
                               bucket)
            if device is not None:
                padded = tuple(jax.device_put(a, device) for a in padded)
            out = _sweep_jit(banks, *padded, n_pad, table.has_knn)
        totals += _fetch(out, bucket)
    return faults.corrupt("devicecost.fused", totals[:, :n_segments])
