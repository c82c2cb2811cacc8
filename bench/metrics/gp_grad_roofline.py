"""Share of the gp_grad kernel's roofline: the least time its calls of the
traced jobs could take (operations over the bf16 peak or bytes over HBM
bandwidth, whichever is larger; counted from the algorithm's shapes in
``counts.py``) over the summed device time of the trace's ``gp_grad``
operations."""

import counts

#: The kernel's calls in the trace: its Pallas call or the jitted wrapper
#: that holds it (``kernels/ops.py``), as the HLO instruction names them.
NAMES = r"(grad_mean\w*_kernel|gp_grad[\w.]*?)(\.\d+)?"


def read(ctx):
    t = ctx.reduced
    if t is None or ctx.peaks is None:
        return None
    seconds = t.seconds_of(NAMES)
    calls = counts.kernel_calls_per_round(ctx.cell.traffic["algo"], ctx.settings)["gp_grad"]
    if seconds <= 0 or calls == 0:
        return None
    share, _ = counts.roofline_share(counts.kernel_call("gp_grad", ctx.settings),
                                     calls * ctx.rounds_done, seconds, ctx.peaks)
    return 100.0 * share
