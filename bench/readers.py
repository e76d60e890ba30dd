"""Arithmetic that several metrics' readers share (``metrics/<name>.py``)."""
from __future__ import annotations

import numpy as np


def latencies_ms(ctx) -> np.ndarray:
    """Every answered request's latency, from its due time, in ms."""
    return np.asarray([r.latency_s for r in ctx.answered]) * 1e3


def stage_mean_ms(ctx, stage: str):
    """Mean of one of the gateway's stage histograms over the window."""
    n, total = ctx.counters["stages"].get(stage, (0, 0.0))
    return total / n if n else None


def kernel_seconds(ctx):
    """(device seconds, calls) of the tree-walk kernels in the trace."""
    if ctx.trace is None or not ctx.trace.kernel_calls:
        return None, 0
    return sum(s for _, s in ctx.trace.kernel_calls), len(ctx.trace.kernel_calls)


def least_seconds(ctx):
    """Summed least time of the window's engine batches (``bench/work.py``),
    and the roof that bounds it; None without per-batch rows."""
    from bench.work import least_time_of_batches

    if not ctx.batch_rows or ctx.peaks is None:
        return None, None
    return least_time_of_batches(ctx.work, ctx.batch_rows, ctx.peaks)
