"""Median latency from the due time to the answer, over all requests of
the window (ms).  A refused or failed request has no answer and counts as
later than any; where they are half or more, there is no median to read."""
import numpy as np

from bench.readers import latencies_ms


def read(ctx):
    lost = sum(1 for r in ctx.window.records if not r.ok)
    lat = np.concatenate([latencies_ms(ctx), np.full(lost, np.inf)])
    p50 = float(np.median(lat)) if len(lat) else float("inf")
    return p50 if np.isfinite(p50) else None
