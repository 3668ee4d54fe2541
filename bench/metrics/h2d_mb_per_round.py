"""Megabytes uploaded host-to-device per update: client shards, eval
batches, local-step counts, aggregation weights and rng slabs, counted
by the program where each upload happens (`sim.h2d_bytes`, bytes on the
device) and carried on its `sim.run` spans. None where the program
carries no such count."""
from bench.runs import run_total


def read(ctx):
    total = run_total(ctx.spans, "h2d_bytes")
    return None if total is None else total / 1e6 / ctx.obs_updates
