"""Executables compiled or fetched from the persistent cache while the
traced run's calls ran (JAX's monitoring events). 0 when set-up warmed
every shape."""


def read(ctx):
    return ctx.window_compiles
