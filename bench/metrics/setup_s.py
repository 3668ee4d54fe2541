"""Seconds from process start to the window's start (host clock): device
init, access windows, data, building the entry, and the warm-up call."""


def read(ctx):
    return ctx.setup_s
