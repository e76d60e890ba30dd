"""Load generator: 99th percentile of how late each send ran against its
due time (ms).  A late generator is read here, not as a slow server."""
import numpy as np


def read(ctx):
    late = [r.late_s for r in ctx.window.records if r.t_sent]
    return float(np.percentile(late, 99)) * 1e3 if late else None
