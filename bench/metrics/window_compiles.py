"""Launcher: XLA compiles inside the traced window (JAX's
``backend_compile_duration`` events).  Moves ``tokens_per_s``: a compile
in the window stalls every request behind it."""


def read(ctx):
    return float(ctx.compiles)
