"""Host milliseconds per update in selection and planning: the program's
`sim.select` spans (training and evaluation stages) and, in the batched
executor, its `sim.batched.plan` span, each counted once."""
from bench.spans import total_ms


def read(ctx):
    ms = total_ms(ctx.spans, {"sim.select", "sim.batched.plan"})
    return None if ms is None else ms / ctx.obs_updates
