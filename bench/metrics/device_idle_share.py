"""1 - (union of the device's operation intervals) / traced window, in %."""


def read(ctx):
    t = ctx.reduced
    if t is None:
        return None
    return 100.0 * t.idle_share
