"""Percent of the profiled stretch in which no operation ran on the
device, averaged over the cell's chips (profiler trace, `repro.obs`
off)."""


def read(ctx):
    p = ctx.profile
    return None if p is None else 100.0 * p["idle_share"]
