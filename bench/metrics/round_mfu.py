"""The whole round's share of the chip's peak: operations per round from
``counts.py`` times the rounds of the traced window, over the window times
chips times the published bf16 peak.  The round body runs f32 products at
HIGHEST precision (several bf16 passes each), so its attainable share is a
fraction of that peak."""

import counts


def read(ctx):
    t = ctx.reduced
    if t is None or ctx.peaks is None:
        return None
    flops = counts.round_flops(ctx.cell.traffic["algo"], ctx.settings, ctx.query_flops)
    return 100.0 * flops * ctx.rounds_done / (
        t.window_s * ctx.n_chips * ctx.peaks["bf16_flops_per_s"])
