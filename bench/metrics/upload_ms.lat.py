"""Host-to-device upload: mean host time of one batch's ``upload``
stage inside the dispatch (``repro.obs.stages``; ms), in the latency cell."""
from bench.readers import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "upload")
