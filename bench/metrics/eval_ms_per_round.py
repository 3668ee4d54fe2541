"""Milliseconds per update in `sim.eval` spans that evaluated a trained
model (one host sync each)."""
from bench.spans import total_ms


def read(ctx):
    ms = total_ms(ctx.spans, {"sim.eval"},
                  where=lambda s: s["args"].get("trained"))
    return None if ms is None else ms / ctx.obs_updates
