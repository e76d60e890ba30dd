"""Device wait and readback: mean host time of one batch's ``fetch``
stage inside the dispatch (``repro.obs.stages``; ms), in the latency cell."""
from bench.readers import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "fetch")
