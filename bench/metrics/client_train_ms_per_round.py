"""Milliseconds per update in `sim.client_train` spans. With tracing on
the program blocks on the device there, so the device time is inside."""
from bench.spans import total_ms


def read(ctx):
    ms = total_ms(ctx.spans, {"sim.client_train"})
    return None if ms is None else ms / ctx.obs_updates
