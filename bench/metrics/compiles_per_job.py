"""Compile events per window job: JAX's backend-compile event, which the
program's ``steady_state_guard`` counts and which JAX also records when the
persistent cache serves the executable."""


def read(ctx):
    jobs = ctx.jobs
    return sum(j.compiles for j in jobs) / len(jobs) if jobs else None
