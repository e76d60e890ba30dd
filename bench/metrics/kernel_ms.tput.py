"""Kernel: device time of one tree-walk kernel call (``tree_traverse_*``
events of the trace, ms), in the throughput cells."""
from bench.readers import kernel_seconds


def read(ctx):
    seconds, calls = kernel_seconds(ctx)
    return seconds / calls * 1e3 if calls else None
