"""The work a sweep needs, counted from its designs and points alone.

A (designs x points) sweep needs, at the least, each design's access
records once -- a model id and a design id per record -- then a size and
a weight per record at every point, and writes one cost per cell.  Every
quantity is 4 bytes.  The records are those the plain reference emits
(:mod:`bench.reference`), unpadded: nothing here depends on how the
program pads, chunks, tiles or shards a sweep, so a change to any of
those cannot move the yardstick.  A fused sweep is bounded by memory
(a few operations per byte, far under the chip's ratio of peak
operations to peak bandwidth), so these bytes over the HBM peak are the
least time the chip could take.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

from bench.reference import Design, Workload, op_records

WORD = 4


class SweepWork:
    """Bytes of sweeps, with each design's record count remembered."""

    def __init__(self) -> None:
        self._records: Dict[Tuple, int] = {}

    def records(self, design: Design, wl: Workload,
                ops: Sequence[str]) -> int:
        key = (design, wl, tuple(ops))
        n = self._records.get(key)
        if n is None:
            n = sum(len(op_records(op, design, wl)) for op in ops)
            self._records[key] = n
        return n

    def sweep_bytes(self, designs: Sequence[Design],
                    points: Sequence[Tuple[Workload, Dict[str, float]]]
                    ) -> int:
        per_point = [sum(self.records(d, wl, sorted(mix)) for d in designs)
                     for wl, mix in points]
        layout = 2 * WORD * per_point[0]          # model id + design id
        values = 2 * WORD * sum(per_point)        # size + weight
        cells = WORD * len(points) * len(designs)
        return layout + values + cells
