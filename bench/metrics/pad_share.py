"""Engine buckets: share of the executed rows that are bucket padding (%)."""


def read(ctx):
    c = ctx.counters
    if not c["padded_rows"]:
        return None
    return 100.0 * (1.0 - c["batched_rows"] / c["padded_rows"])
