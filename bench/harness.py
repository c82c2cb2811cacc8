"""One run of one benchmark cell: set-up, a timed window of jobs, the check.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) pairs a
configuration (``configs/<name>.json``) with a traffic mix
(``traffic/<name>.json``).  Everything particular to one of them lives in
files found by name:

* ``configs/<config>.json``   -- sizes, the objective kind, algorithm settings;
* ``objectives/<kind>.py``    -- makes the objective's data or weights from the
  seed on the device, hands the program its query and value functions, and
  holds the plain reference of the objective;
* ``traffic/<traffic>.json``  -- the algorithm, rounds per job, chunk, target;
* ``reference/<algo>.py``     -- the plain reference of the algorithm's rounds;
* ``limits/<cell>.json``      -- the limits of the comparison that decides
  ``correct``, with the readings they were set from;
* ``metrics/<metric>.py``     -- one reader per metric of ``BENCHMARK.json``.

The window is a closed loop of jobs from one caller: a job is one call of
``algorithms.simulate`` for the traffic's rounds, ended when its
``SimResult`` is on the host; job ``j`` takes key ``fold_in(seed, j)``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: Job index of the warm-up job: never a window job's index.
WARMUP_JOB = 2**30


def keep_logs_in_checkout(root: str = ROOT) -> None:
    """Point the TPU runtime's logs into the checkout (it would write to a
    fixed directory under /tmp); call before JAX starts."""
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(root, ".bench_trace", "tpu_logs"))


class NoAccelerator(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration and traffic."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    spec: dict  # the whole BENCHMARK.json
    root: str

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if self.name in m.get("workloads", [self.name])]

    @property
    def per_layer(self) -> list[dict]:
        return [m for m in self.spec["per_layer"] if self.name in m.get("workloads", [self.name])]


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a file of the benchmark by its path (its name may hold dots)."""
    name = name or "bench_file_" + "".join(c if c.isalnum() else "_" for c in path)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_file(root: str, *parts: str) -> str:
    path = os.path.join(root, "bench", *parts)
    if not os.path.exists(path):
        raise FileNotFoundError(f"benchmark file {os.path.relpath(path, root)} is missing")
    return path


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Find cell ``name`` in ``<root>/BENCHMARK.json`` and its files by name."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(by_name)})")
    w = by_name[name]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(bench_file(root, "configs", f"{w['config']}.json")),
        traffic=_read_json(bench_file(root, "traffic", f"{w['traffic']}.json")),
        limits=_read_json(bench_file(root, "limits", f"{name}.json")),
        spec=spec,
        root=root,
    )


def require_tpu(chips: int):
    """The devices of a chip run; raise ``NoAccelerator`` without a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: str) -> str:
    """Persistent compilation cache at a fixed path inside the checkout.

    ``JAX_COMPILATION_CACHE_DIR``, where set, wins (jax reads it itself).
    Every executable is cached, however quickly it compiled, so that a
    second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits (no two collide)."""
    import jax

    if seed < 0 or seed >= 2**64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


# ---------------------------------------------------------------------------
# the system under test, as the window drives it
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Problem:
    """What an objective module hands the harness (see ``objectives/``)."""

    cobjs: Any  # stacked per-client parameters, the program's type
    query: Any  # query(cobj, x, key) -> noisy scalar
    global_value: Any  # global_value(cobjs, x) -> F(x)
    dim: int
    fstar: Optional[float]  # known optimum of F, or None
    data: dict  # the arrays the benchmark made from the seed (the reference's input)


def scoped(fn, scope: str = "bench.objective"):
    """``fn`` traced under ``jax.named_scope(scope)``: the trace reduction
    attributes device time to the objective by this name."""
    import jax

    @functools.wraps(fn)
    def wrapped(*args):
        with jax.named_scope(scope):
            return fn(*args)

    return wrapped


def build_problem(cell: Cell, key) -> Problem:
    mod = load_module(bench_file(cell.root, "objectives", f"{cell.config['objective']}.py"))
    prob = mod.build(cell.config, key)
    return dataclasses.replace(prob, query=scoped(prob.query),
                               global_value=scoped(prob.global_value))


def algo_settings(cell: Cell, dim: int) -> dict:
    """The algorithm's settings: the configuration's, then the traffic's,
    with the cell's clients and dimension."""
    s = dict(cell.config.get("algo", {}))
    s.update(cell.traffic.get("algo_settings", {}))
    s.update(n_clients=int(cell.config["n_clients"]), dim=int(dim))
    return s


def algo_config(cell: Cell, dim: int):
    from repro.core.algorithms import AlgoConfig

    return AlgoConfig(name=cell.traffic["algo"], **algo_settings(cell, dim))


@dataclasses.dataclass
class JobRecord:
    index: int
    start: float  # host clock, seconds
    end: float
    rounds: int
    compiles: int  # backend-compile events during the job (cache hits count)
    cache_misses: int  # compile requests the persistent cache did not serve
    result: Any  # SimResult of numpy arrays
    error: Optional[str] = None
    state: Optional[dict] = None  # client state after the first chunk (``first_chunk_state``)


class CompileCounter:
    """Counts JAX's compile events while active (a program counter).

    ``compiles`` counts ``/jax/core/compile/backend_compile_duration``, the
    event the program's ``steady_state_guard`` counts; JAX records it for a
    persistent-cache hit too.  ``cache_misses`` counts compile requests that
    the persistent cache did not serve, i.e. real compilations."""

    _installed = False
    _active: list = []

    def __init__(self):
        self.compiles = 0
        self.requests = 0
        self.hits = 0

    @property
    def cache_misses(self) -> int:
        return self.requests - self.hits

    @classmethod
    def _install(cls):
        if cls._installed:
            return
        import jax

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                for c in cls._active:
                    c.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                for c in cls._active:
                    c.requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                for c in cls._active:
                    c.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        cls._installed = True

    def __enter__(self):
        self._install()
        CompileCounter._active.append(self)
        return self

    def __exit__(self, *exc):
        CompileCounter._active.remove(self)
        return False


class Runner:
    """Runs the cell's jobs through the program's front door."""

    def __init__(self, cell: Cell, seed: int, devices):
        import jax

        self.cell = cell
        self.seed = seed
        self.devices = devices
        self.key = seed_key(seed)
        k_obj, self.k_jobs = jax.random.split(self.key)
        with jax.default_device(devices[0]):
            self.problem = build_problem(cell, k_obj)
            jax.block_until_ready(self.problem.cobjs)
        self.cfg = algo_config(cell, self.problem.dim)
        self.rounds = int(cell.traffic["rounds"])

    def job_key(self, j: int):
        import jax

        return jax.random.fold_in(self.k_jobs, j)

    def run_job(self, j: int, keep_state: bool = False) -> JobRecord:
        """Job ``j``; with ``keep_state`` the client state after its first
        chunk is kept for the check (``first_chunk_state``)."""
        import jax
        import numpy as np

        from repro.core import algorithms as alg

        t = self.cell.traffic
        p = self.problem
        err, host, kept = None, None, {}
        tap = first_chunk_state(kept) if keep_state else contextlib.nullcontext()
        with CompileCounter() as cc, jax.profiler.TraceAnnotation("bench.job"):
            start = time.perf_counter()
            try:
                with jax.default_device(self.devices[0]), tap:
                    res = alg.simulate(self.cfg, self.job_key(j), p.cobjs, p.query,
                                       p.global_value, self.rounds, chunk=int(t["chunk"]),
                                       eval_every=int(t["eval_every"]))
                    host = jax.tree_util.tree_map(np.asarray, jax.device_get(res))
            except Exception as e:  # a job that raises is a failed job, not a crash
                err = f"{type(e).__name__}: {e}"
            end = time.perf_counter()
        return JobRecord(j, start, end, self.rounds, cc.compiles, cc.cache_misses, host, err,
                         kept or None)


@functools.lru_cache(maxsize=None)
def _device_copy():
    """One jitted copy for every run's kept states, so that it compiles once,
    in the warm-up job."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))


@contextlib.contextmanager
def first_chunk_state(out: dict):
    """While active, the client states that the first chunk step of a front
    door call returns are copied on the device into ``out``: the trajectory
    (``traj_x``, ``traj_y``, ``count``), ``key``, ``w_local`` and
    ``w_global`` of every client, the fields the check reads.  The copy is
    taken where the front door hands those states to its chunk-boundary
    repair (``rounds.boundary_repair_on_device``), before the next chunk
    step takes them over (it donates them); nothing wraps the chunk step,
    whose tracing the window times."""
    from repro.core import rounds as rounds_mod

    orig = rounds_mod.boundary_repair_on_device
    copy = _device_copy()

    def repair(states, *args, **kw):
        if not out:
            out.update(copy({"traj_x": states.traj.xs, "traj_y": states.traj.ys,
                             "count": states.traj.count, "key": states.key,
                             "w_local": states.w_local, "w_global": states.w_global}))
        return orig(states, *args, **kw)

    rounds_mod.boundary_repair_on_device = repair
    try:
        yield out
    finally:
        rounds_mod.boundary_repair_on_device = orig


def target_round(f_values, f0: float, fstar: float, closed: float) -> Optional[int]:
    """First round r with F(x_r) - f* <= (1 - closed) (F(x_0) - f*), or None."""
    import numpy as np

    bar = fstar + (1.0 - closed) * (f0 - fstar)
    hit = np.nonzero(np.asarray(f_values) <= bar)[0]
    return int(hit[0]) if hit.size else None


def job_failed(rec: JobRecord, problem: Problem, traffic: dict) -> bool:
    import numpy as np

    if rec.error is not None or rec.result is None:
        return True
    f = np.asarray(rec.result.f_values)
    if not np.isfinite(f).all():
        return True
    closed = traffic.get("target_gap_closed")
    if closed is not None and problem.fstar is not None:
        return target_round(f, float(f[0]), problem.fstar, closed) is None
    return False


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunContext:
    """Everything a metric reader may read (``metrics/<name>.py``)."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    setup_s: float
    jobs: list
    window_s: float
    problem: Problem
    cfg: Any
    n_chips: int
    device_kind: str
    settings: dict = dataclasses.field(default_factory=dict)  # the algorithm's settings
    query_flops: float = 0.0  # operations of one objective query
    peaks: Optional[dict] = None
    reduced: Optional[Any] = None  # trace_reduce.TraceSummary of the window

    @property
    def ok_jobs(self) -> list:
        return [j for j in self.jobs if j.result is not None]

    @property
    def rounds_done(self) -> int:
        return sum(j.rounds for j in self.ok_jobs)


#: The window's first jobs keep their state after their first chunk; the
#: check samples one of them by the seed (a traced window holds two).
KEPT_JOBS = 2


def run_window(runner: Runner, seconds: float) -> tuple[list, float]:
    """Jobs back to back until ``seconds`` have passed; the job running at
    the deadline is completed and counted."""
    jobs = []
    t0 = time.perf_counter()
    j = 0
    while True:
        jobs.append(runner.run_job(j, keep_state=j < KEPT_JOBS))
        j += 1
        if jobs[-1].end - t0 >= seconds:
            break
    return jobs, jobs[-1].end - jobs[0].start


def trace_dir(root: str, cell: str, seed: int) -> str:
    return os.path.join(root, ".bench_trace", cell, str(seed))


def kernel_call_count(hlo_text: str) -> int:
    """Number of Mosaic kernel calls in a compiled program's HLO."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


class ChunkSpy:
    """Wraps the chunk steps the front door builds (``rounds.make_chunk_step``),
    from outside the program: each call runs under a ``bench.chunk_step``
    host annotation, and the first call's argument shapes are kept so that
    the compiled step's HLO can be read after the window."""

    def __init__(self):
        self.first = None  # (step, argument shapes)

    def __enter__(self):
        import jax

        from repro.core import rounds as rounds_mod

        self._mod, self._orig = rounds_mod, rounds_mod.make_chunk_step
        spy = self

        def make_chunk_step(chunk_fn):
            step = spy._orig(chunk_fn)

            def call(*args):
                if spy.first is None:
                    spy.first = (step, jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                        args))
                with jax.profiler.TraceAnnotation("bench.chunk_step"):
                    return step(*args)

            return call

        rounds_mod.make_chunk_step = make_chunk_step
        return self

    def __exit__(self, *exc):
        self._mod.make_chunk_step = self._orig
        return False

    def hlo_text(self) -> str:
        """The compiled chunk step's HLO (the persistent cache serves it)."""
        step, shapes = self.first
        return step.lower(*shapes).compile().as_text()


#: Jobs a traced window runs at most, so the trace and its reduction stay small.
TRACED_JOBS = 2


def traced_window(runner: "Runner", seconds: float, tdir: str, log):
    """The window under the profiler: at most ``TRACED_JOBS`` jobs.  Host
    Python calls are not traced; the benchmark's annotations are."""
    import jax

    import trace_reduce

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with ChunkSpy() as spy:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            jobs, t0 = [], time.perf_counter()
            while not jobs or (len(jobs) < TRACED_JOBS and time.perf_counter() - t0 < seconds):
                jobs.append(runner.run_job(len(jobs), keep_state=len(jobs) < KEPT_JOBS))
        finally:
            jax.profiler.stop_trace()
    hlo = spy.hlo_text()
    log(f"chunk step: {kernel_call_count(hlo)} tpu_custom_call(s)")
    t = time.perf_counter()
    reduced = trace_reduce.reduce_dir(tdir, op_names=trace_reduce.op_names_from_hlo(hlo),
                                      n_devices=len(runner.devices))
    log(f"trace reduced in {time.perf_counter() - t:.1f} s")
    return jobs, jobs[-1].end - jobs[0].start, reduced


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compute_metrics(ctx: RunContext, specs: list[dict]) -> dict:
    """Each metric by its own reader; a reader that finds nothing returns None
    and the metric is left out."""
    out = {}
    for m in specs:
        mod = load_module(bench_file(ctx.cell.root, "metrics", f"{m['name']}.py"))
        v = mod.read(ctx)
        if v is None:
            continue
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} read {v}")
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, log=print) -> dict:
    """Set up, measure, check; return the result object (the last line)."""
    import numpy as np

    import check
    import peaks as peaks_mod

    enable_compile_cache(cell.root)
    kind = devices[0].device_kind
    peaks = peaks_mod.for_device(kind) if devices[0].platform == "tpu" else None
    runner = Runner(cell, seed, devices)
    warm = runner.run_job(WARMUP_JOB, keep_state=True)  # compiles the state's copy
    if warm.error is not None:
        raise RuntimeError(f"warm-up job failed: {warm.error}")
    warm.state = None
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} warm-up compiles={warm.compiles} "
        f"cache_misses={warm.cache_misses} job_s={warm.end - warm.start:.3f}")

    reduced = None
    if trace:
        tdir = trace_dir(cell.root, cell.name, seed)
        try:
            jobs, window_s, reduced = traced_window(runner, seconds, tdir, log)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        jobs, window_s = run_window(runner, seconds)
    mem = memory_peak_bytes(devices)
    for rec in jobs:  # the kept state to the host: nothing of the program stays
        if rec.state is not None:
            rec.state = {k: np.asarray(v) for k, v in rec.state.items()}
    for rec in jobs:
        log(f"job {rec.index}: {rec.end - rec.start:.3f} s, compiles={rec.compiles}, "
            f"cache_misses={rec.cache_misses}" + (f", error={rec.error}" if rec.error else ""))

    obj = load_module(bench_file(cell.root, "objectives", f"{cell.config['objective']}.py"))
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace, setup_s=setup_s,
                     jobs=jobs, window_s=window_s, problem=runner.problem, cfg=runner.cfg,
                     n_chips=len(devices), device_kind=kind,
                     settings=algo_settings(cell, runner.problem.dim),
                     query_flops=obj.query_flops(cell.config), peaks=peaks, reduced=reduced)
    failed = sum(job_failed(r, runner.problem, cell.traffic) for r in jobs)
    metrics = compute_metrics(ctx, cell.per_layer if trace else cell.end_to_end)

    # the check runs after the peak was read; the window's results are on
    # the host and nothing of the program's state is left on the device
    t = time.perf_counter()
    numbers = check.check(cell, seed, runner.problem, runner.cfg, jobs, runner.job_key,
                          devices[0])
    log(f"check: {time.perf_counter() - t:.1f} s")
    correct = all(n["value"] <= n["limit"] for n in numbers.values())

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(jobs), "failed": int(failed),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    out["checks"] = numbers
    return out
