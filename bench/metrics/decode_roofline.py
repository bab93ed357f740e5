"""Model step: share (%) of the memory roofline that the decode
executions reach.  The bytes are what the algorithm needs, from the
configuration and the window's context lengths (the family's
``decode_bytes``; for the dense family every layer's and the head's
weights once per execution, and each active row's filled keys and
values, embedding row and float32 logits per step), over 819 GB/s, over
the measured device time of every decode execution in the window.
Moves ``tokens_per_s``."""

from bench import cost
from bench.metrics import DECODE


def read(ctx):
    if ctx.trace is None:
        return None
    runs = [d for ds in ctx.modules(DECODE).values() for d in ds]
    if not runs:
        return None
    need = cost.decode_bytes(ctx.cfg, len(runs),
                             [c for _, c in ctx.decode_steps()], ctx.root)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (1e-9 * sum(runs))
