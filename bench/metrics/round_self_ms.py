"""Milliseconds per update inside `sim.round` spans but outside their
child spans: gathering the clients' shards, host-to-device uploads and
the round loop's bookkeeping. The batched planner's own `sim.round` spans
(mode `batched_plan`) are planning, which `select_ms_per_round` holds."""
from bench.spans import self_ms


def read(ctx):
    ms = self_ms(ctx.spans, "sim.round",
                 where=lambda s: s["args"].get("mode") != "batched_plan")
    return None if ms is None else ms / ctx.obs_updates
