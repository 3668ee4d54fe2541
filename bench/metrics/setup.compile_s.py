"""Seconds of set-up in XLA compiles and persistent-cache retrievals, as
JAX's monitoring events report them."""


def read(ctx):
    return ctx.parts["compile_s"]
