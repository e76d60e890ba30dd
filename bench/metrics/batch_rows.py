"""Micro-batcher: real rows per engine batch over the window."""


def read(ctx):
    c = ctx.counters
    return c["batched_rows"] / c["batches"] if c["batches"] else None
