"""Endpoint: device us of the bucketed prefill programs per prompt token
prefilled in the window (padding is the program's cost, not the
prompt's).  Moves ``latency_p95_ms``, through the time to the first
token."""

from bench.metrics import PREFILL


def read(ctx):
    if ctx.trace is None:
        return None
    ns = sum(sum(d) for d in ctx.modules(PREFILL).values())
    toks = sum(r.prompt_len for r in ctx.prefilled())
    return 1e-3 * ns / toks if ns and toks else None
