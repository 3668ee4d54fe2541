"""The busiest held expert's token-picks over the mean held expert's:
counter `moe.local_picks_max` (the most picks on one held expert, per
MoE layer and local step, summed) over `moe.local_picks_mean` (the mean
over the held experts, summed the same way). 1 is an even load; the
grouped matmul's time follows the busiest expert. Both counters are
carried on the `sim.run` spans; None where the program counts no picks."""
from bench.runs import run_total


def read(ctx):
    busiest = run_total(ctx.spans, "local_picks_max")
    mean = run_total(ctx.spans, "local_picks_mean")
    if not busiest or not mean:
        return None
    return busiest / mean
