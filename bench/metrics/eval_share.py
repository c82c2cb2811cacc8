"""Share of the device-busy time spent evaluating F(x_{r+1}) inside the
round: operations whose name the chunk step's HLO gives the program's
``fzoo.eval`` scope, over busy time (``phases.scope_share``).  An upper
bound: an instruction of another executable that shares a name with one of
the chunk step's counts too."""

import phases


def read(ctx):
    return phases.scope_share(ctx.reduced, "fzoo.eval")
