"""Control plane: share (%) of the requests finished in the window that
the deepest tier served.  Moves ``latency_p95_ms``: each offloaded
request pays the link and the cloud's charged latency."""


def read(ctx):
    done = ctx.finished()
    if not done:
        return None
    return 100.0 * sum(r.tier == ctx.cloud_tier for r in done) / len(done)
