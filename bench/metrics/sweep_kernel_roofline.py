"""The fused sweep scorer's share of its roofline.

The least time is the bytes the sweeps answered need (bench/work.py:
unpadded records and their per-point sizes and weights, plus the
output cells) over the chip's HBM peak: the sweep does a few operations
per byte, far below the chip's ratio of peak operations to bandwidth,
so memory bounds it.  Divided by the fused programs' device time summed
over the chips, so on several chips it is each chip's share of its own
peak."""


def read(ctx):
    trace, work = ctx["trace"], ctx["work_bytes"]
    if not trace or not trace["fused_s"] or not work:
        return None
    return 100.0 * (work / ctx["peaks"]["hbm_bytes_per_s"]) \
        / trace["fused_s"]
