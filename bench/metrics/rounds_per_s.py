"""Global-model updates (rounds or FedBuff flushes) completed per second
of the window: all updates of the window's calls over the time from the
first call's start to the last call's end (host clock)."""


def read(ctx):
    return ctx.window["updates"] / ctx.window["seconds"]
