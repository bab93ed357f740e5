"""Endpoint: device ms per execution of the cloud endpoint's jitted
decode step, from the trace.  Moves ``tokens_per_s``."""

from bench.metrics import DECODE


def read(ctx):
    if ctx.trace is None or ctx.cloud_decode is None:
        return None
    runs = ctx.modules(DECODE).get(ctx.cloud_decode, [])
    return 1e-6 * sum(runs) / len(runs) if runs else None
