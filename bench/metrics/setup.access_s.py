"""Seconds of set-up spent computing the access windows
(`repro.orbits.compute_access_windows`), on the host clock."""


def read(ctx):
    return ctx.parts["access_s"]
