"""Share of the device-busy time spent in operations traced under the
benchmark's ``jax.named_scope("bench.objective")`` around the query and
global-value functions it hands the program."""


def read(ctx):
    t = ctx.reduced
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.scope_s.get("bench.objective", 0.0) / t.busy_s
