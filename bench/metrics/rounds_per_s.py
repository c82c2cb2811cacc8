"""All rounds of all jobs of the window over the window's wall time, from
the first job's start to the last job's result on the host."""


def read(ctx):
    return ctx.rounds_done / ctx.window_s
