"""Host wall time of a job minus the device-busy time of its chunk-step
executions (the trace's ``bench.job`` spans and ``jit_run`` modules), the
mean over the traced window's jobs."""


def read(ctx):
    t = ctx.reduced
    if t is None or not t.jobs:
        return None
    per_job = [(e - s) - c for (s, e), c in zip(t.jobs, t.chunk_busy_in_jobs)]
    return sum(per_job) / len(per_job)
