"""Rows answered over the window: every answered request started in the
window, over the time from its first due time to its last answer."""


def read(ctx):
    done = ctx.answered
    if not done:
        return None
    end = max(r.t_done for r in done)
    return sum(r.rows for r in done) / (end - ctx.window.t0)
