"""Useful local-SGD steps over executed ones, in percent. Useful:
`epochs * max(1, n_k // batch)` clipped to `max_steps`, per client and
update. Executed: the power-of-two step bound times every stacked slot
(padded clients, mesh slots, finished scenarios' lanes)."""


def read(ctx):
    useful, executed = ctx.steps
    return 100.0 * useful / executed if executed else None
