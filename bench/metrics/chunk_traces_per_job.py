"""Traces of the chunk function per front-door call: the program's
``chunk_traces`` counter (``repro.core.telemetry``), read after the traced
window, over the calls the run made by then (the warm-up job and the
window's jobs, all of one shape).  A program without the counter reads
nothing."""

import sys


def read(ctx):
    telemetry = sys.modules.get("repro.core.telemetry")
    if telemetry is None or not ctx.jobs:
        return None
    traces = telemetry.counters().get("chunk_traces")
    return None if traces is None else traces / (len(ctx.jobs) + 1)
