"""Milliseconds per executed local-SGD step of a streamed round:
`sim.client` span walls (each one program that trains the round's
clients one after another and folds each result into the round's
accumulator, so the aggregation is inside) over the local steps they
executed, their `steps` args. With tracing on the program blocks on the
device there, so the device time is inside. None where the program
streams no round."""


def read(ctx):
    spans = [s for s in ctx.spans if s["name"] == "sim.client"]
    steps = sum(s["args"].get("steps", 0) for s in spans)
    if not steps:
        return None
    return sum(s["dur_us"] for s in spans) / 1e3 / steps
