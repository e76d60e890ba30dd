"""Plan and backend host path: mean wall time of one batch's dispatch
(table upload, kernel, readback; the gateway's ``shard`` stage, ms), in
the throughput cell."""
from bench.readers import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "shard")
