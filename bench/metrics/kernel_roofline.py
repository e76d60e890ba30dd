"""Kernel: the forest's least time over the kernels' device time (%).

The least time of each batch is ``bench/work.py``'s, from the forest's
real nodes and the chip's peaks in ``bench/peaks.json``; the roof that
bounds it is logged."""
import sys

from bench.readers import kernel_seconds, least_seconds


def read(ctx):
    seconds, calls = kernel_seconds(ctx)
    least, bound = least_seconds(ctx)
    if not calls or least is None:
        return None
    print(f"kernel_roofline: bound by {bound}", file=sys.stderr)
    return 100.0 * least / seconds
