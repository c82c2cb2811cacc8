"""Device-busy time of the chunk-step executions in the traced window over
the rounds they ran, in milliseconds."""


def read(ctx):
    t = ctx.reduced
    if t is None or t.chunk_busy_s <= 0 or ctx.rounds_done == 0:
        return None
    return 1e3 * t.chunk_busy_s / ctx.rounds_done
