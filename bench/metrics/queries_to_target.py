"""Per-client queries at the first round where F(x_r) - F* <= (1 - c)(F(x_0) - F*),
c the traffic's share of the gap to close; the mean over the window's jobs.
A job that misses the target within its rounds counts its whole budget
(and is counted in ``failed``)."""

import numpy as np

from harness import target_round


def read(ctx):
    closed = ctx.cell.traffic.get("target_gap_closed")
    if closed is None or ctx.problem.fstar is None or not ctx.ok_jobs:
        return None
    vals = []
    for rec in ctx.ok_jobs:
        f = np.asarray(rec.result.f_values)
        q = np.asarray(rec.result.queries)
        r = target_round(f, float(f[0]), ctx.problem.fstar, closed)
        vals.append(float(q[r - 1]) if r else float(q[-1]))
    return float(np.mean(vals))
