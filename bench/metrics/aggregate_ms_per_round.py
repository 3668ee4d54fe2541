"""Milliseconds per update in `sim.aggregate` spans (host and batched
executors; the mesh step folds aggregation into its psum)."""
from bench.spans import total_ms


def read(ctx):
    ms = total_ms(ctx.spans, {"sim.aggregate"})
    return None if ms is None else ms / ctx.obs_updates
