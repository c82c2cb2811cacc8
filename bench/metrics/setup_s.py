"""Set-up time: process start until the window's first job can start
(imports, device start, the objective from the seed, one warm-up job)."""


def read(ctx):
    return ctx.setup_s
