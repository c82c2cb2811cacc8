"""Share of the device-busy time spent in the round's local phase: operations
whose name the chunk step's HLO gives the program's ``fzoo.local`` scope
(its update, score and grad sub-scopes included), over busy time
(``phases.scope_share``).  An upper bound: an instruction of another
executable that shares a name with one of the chunk step's counts too."""

import phases


def read(ctx):
    return phases.scope_share(ctx.reduced, "fzoo.local")
