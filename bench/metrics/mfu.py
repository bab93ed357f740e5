"""Whole step: model FLOP/s utilisation (%) over the window: the
operations of every prompt prefilled and every token decoded in it (two
per weight, attention over the filled context, the head where logits are
needed), over the window's seconds, over the chip's bf16 peak.  Moves
``tokens_per_s``."""

from bench import cost


def read(ctx):
    lo, hi = ctx.window
    flops = (sum(cost.prefill_flops(ctx.cfg, r.prompt_len, ctx.root)
                 for r in ctx.prefilled())
             + sum(cost.token_flops(ctx.cfg, c, root=ctx.root)
                   for _, c in ctx.decode_steps()))
    if flops == 0:
        return None
    return 100.0 * flops / (hi - lo) / ctx.peaks["flops"]
