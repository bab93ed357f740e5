"""Gateways and scheduler: output tokens per scheduler step, over the
ticks that started in the window (a tick retires everything it admits,
so each request's tokens belong to the tick it finished in).  Moves
``tokens_per_s``."""


def read(ctx):
    lo, hi = ctx.window
    ticks = [t for t in ctx.ticks if lo <= t.start < hi]
    steps = sum(t.steps for t in ticks)
    if steps == 0:
        return None
    toks = sum(r.n_out for r in ctx.records if r.served and any(
        t.start <= r.t_done <= t.end for t in ticks))
    return toks / steps
