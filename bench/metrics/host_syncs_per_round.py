"""Device-to-host reads per update: evaluation accuracies, the batched
sweep's rng keys, recorded and final models, each a `jax.device_get`
that waits for the device, counted by the program (`sim.host_syncs`)
and carried on its `sim.run` spans. None where the program carries no
such count."""
from bench.runs import run_total


def read(ctx):
    total = run_total(ctx.spans, "host_syncs")
    return None if total is None else total / ctx.obs_updates
