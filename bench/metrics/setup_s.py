"""Set-up: from process start to the window's first due request (s)."""


def read(ctx):
    return ctx.setup_s
