"""Finalize: mean wall time of ``finalize_partials`` per batch (the
gateway's ``finalize`` stage, ms)."""
from bench.readers import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "finalize")
