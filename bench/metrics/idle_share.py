"""Device: share (%) of the window in which no operation ran on the chip
(1 - union of the operations' intervals / window).  Moves
``tokens_per_s``."""

from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - tr.busy_ns(ctx.device().ops, lo, hi) / (hi - lo))
