"""Share of the device-busy time spent at the round's end: operations whose
name the chunk step's HLO gives the program's ``fzoo.round_end`` scope
(round-end active queries, trajectory extension and the RFF w fit), over
busy time (``phases.scope_share``).  An upper bound: an instruction of
another executable that shares a name with one of the chunk step's counts
too."""

import phases


def read(ctx):
    return phases.scope_share(ctx.reduced, "fzoo.round_end")
