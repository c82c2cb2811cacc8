#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_trace_reduce.py`` reduces.

    python3 bench/tests/record_trace.py     # on a TPU; writes data/tiny.xplane.pb

Two ``bench.job`` spans, each running a jitted ``run`` (the chunk step's
executable name) that holds a Pallas kernel named ``gp_score.tiny`` and
operations under ``jax.named_scope("bench.objective")``, with an idle gap
between the jobs.  Also writes ``data/tiny_events.txt``, the device events
the test's hand-computed sums were taken from."""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    def score(x):
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                              name="gp_score.tiny")(x)

    @jax.jit
    def run(x):
        with jax.named_scope("bench.objective"):
            y = jnp.tanh(x @ x)
        return score(y).sum()

    x = jnp.ones((512, 512), jnp.float32)
    run(x).block_until_ready()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.job"):
            run(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
    dst = os.path.join(HERE, "data", "tiny.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tdir)

    import trace_reduce

    with open(os.path.join(HERE, "data", "tiny_events.txt"), "w") as f:
        for p in trace_reduce.load_planes(dst):
            for line, evs in p.lines.items():
                if p.name.startswith("/device:TPU:0") or any(e.name == "bench.job" for e in evs):
                    for e in evs:
                        if e.name == "bench.job" or p.name.startswith("/device:TPU:0"):
                            f.write(f"{p.name}\t{line}\t{e.name}\t{e.start:.9f}\t{e.end:.9f}"
                                    f"\t{e.text[:200]}\n")
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
