"""Useful training FLOPs completed in the profiled stretch over the
stretch's length times the chips times their bf16 peak, in percent.
Useful FLOPs: 3 x forward FLOPs per sample (the configuration's own
count) x batch x useful local-SGD steps."""


def read(ctx):
    p = ctx.profile
    if p is None or not ctx.profile_calls or not ctx.useful_flops_per_call:
        return None
    flops = ctx.useful_flops_per_call * ctx.profile_calls
    return 100.0 * flops / (p["window_s"] * p["chips"]
                            * ctx.peak["bf16_flops_per_s"])
