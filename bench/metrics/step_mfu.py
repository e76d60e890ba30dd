"""Whole step: the forest's least time of every batch of the window over
the traced window (%): the share of the chip's roof the served path
reaches, whichever kernel does the work."""
import sys

from bench.readers import least_seconds


def read(ctx):
    least, bound = least_seconds(ctx)
    if least is None or not ctx.trace_window_s:
        return None
    print(f"step_mfu: bound by {bound}", file=sys.stderr)
    return 100.0 * least / ctx.trace_window_s
