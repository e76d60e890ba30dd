"""Micro-batcher: mean wait from enqueue to dispatch (the gateway's
``queue`` stage histogram over the window, ms)."""
from bench.readers import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "queue")
