"""The comparison that decides ``correct``.

What the window produced (every job's ``SimResult``, and the client state
of one sampled job after its first chunk) is compared with plain references
that import nothing of the program and take only the data the benchmark
made from the seed:

* ``jobs_lost``   -- window jobs that raised and returned nothing (limit 0);
* ``queries_off`` -- rounds of any window job whose cumulative per-client
  query count is not r * (queries per round) (exact, limit 0);
* ``f_gap``       -- the objective: worst gap between the F(x_r) the program
  reported and the objective's plain reference at the same x_r, over every
  round of every window job (``objectives/<kind>.py:f_gaps``), and
  ``f_gap_median``, the median of those gaps;
* the rounds: the sampled job against the algorithm's plain reference
  (``reference/<algo>.py``) run from the same job key for its first
  ``ref_rounds`` rounds at the cell's own sizes, each number the worst over
  those rounds:
  ``x_gap``       |x_r - x_r^ref| / |x_r^ref - x_0|, the server iterate;
  ``step_gap``    the gap of the server's step lengths |x_r - x_{r-1}|;
* the sampled job's client state after its first chunk (FZooS):
  ``w_fit_gap``   worst over clients of |w_i - w_i^ref| / |w_i^ref|, with
                  w_i^ref eq. 6 fitted on the client's own trajectory: the
                  round-end RFF fit;
  ``w_avg_gap``   |w_global - mean_i w_i^ref| / |mean_i w_i^ref|: the
                  server's average of the weights;
  ``x_gap_resume`` x_gap of the round after the chunk, replayed by the
                  reference from that state, where the RFF transfer term is
                  nonzero;
  ``q_gap``       root mean square over the clients' trajectories of the
                  relative gap between the program's answers and the
                  objective's reference at the same points under the same
                  query keys (``objectives/<kind>.py:ref_queries``).
  (``descent_gap``, the gap of the objective's progress, is read by
  ``readings.py`` only: no cell compares it.)

A cell compares the numbers its ``limits/<cell>.json`` names, each against
its limit; the file records the readings the limits were set from.
"""

from __future__ import annotations

import random

import numpy as np

import harness

#: The value of a number that could not be read: it fails any limit.
UNREAD = 1e300


def queries_off(jobs, qpr: int) -> int:
    bad = 0
    for rec in jobs:
        q = np.asarray(rec.result.queries)
        bad += int(np.sum(q != qpr * np.arange(1, q.shape[0] + 1)))
    return bad


def x_gap(xs_prog, xs_ref) -> float:
    """Worst over rounds r >= 1 of |x_r - x_r^ref| / |x_r^ref - x_0|."""
    xs_prog, xs_ref = np.asarray(xs_prog, np.float64), np.asarray(xs_ref, np.float64)
    worst = 0.0
    for r in range(1, xs_ref.shape[0]):
        moved = np.linalg.norm(xs_ref[r] - xs_ref[0])
        worst = max(worst, float(np.linalg.norm(xs_prog[r] - xs_ref[r]) / max(moved, 1e-30)))
    return worst


def step_gap(xs_prog, xs_ref) -> float:
    """Worst over rounds r >= 1 of | |x_r - x_{r-1}| - |x_r^ref - x_{r-1}^ref| |
    over |x_r^ref - x_{r-1}^ref|: the server's step length."""
    xs_prog, xs_ref = np.asarray(xs_prog, np.float64), np.asarray(xs_ref, np.float64)
    worst = 0.0
    for r in range(1, xs_ref.shape[0]):
        ref = np.linalg.norm(xs_ref[r] - xs_ref[r - 1])
        got = np.linalg.norm(xs_prog[r] - xs_prog[r - 1])
        worst = max(worst, abs(got - ref) / max(ref, 1e-30))
    return worst


def descent_gap(f_prog, f_ref) -> float:
    """Worst over rounds r >= 1 of |(F(x_r) - F(x_0)) - (F(x_r^ref) - F(x_0))|
    over |F(x_r^ref) - F(x_0)|: the progress of the objective."""
    f_prog, f_ref = np.asarray(f_prog, np.float64), np.asarray(f_ref, np.float64)
    worst = 0.0
    for r in range(1, f_ref.shape[0]):
        ref = f_ref[r] - f_ref[0]
        worst = max(worst, abs((f_prog[r] - f_prog[0]) - ref) / max(abs(ref), 1e-30))
    return worst


def row_gap(got, ref) -> float:
    """Worst over rows i of |got_i - ref_i| / |ref_i|."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    num = np.linalg.norm(got - ref, axis=-1)
    return float(np.max(num / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)))


def w_gaps(w_local, w_global, w_ref) -> dict:
    """``w_fit_gap`` and ``w_avg_gap`` of weights (N, M) against the fits
    ``w_ref`` (N, M) on the same trajectories."""
    w_ref = np.asarray(w_ref, np.float64)
    mean = np.broadcast_to(w_ref.mean(axis=0), w_ref.shape)
    return {"w_fit_gap": row_gap(w_local, w_ref), "w_avg_gap": row_gap(w_global, mean)}


def q_gap(y_prog, y_ref, count) -> float:
    """Root mean square over the live trajectory slots of the relative gap."""
    y_prog, y_ref = np.asarray(y_prog, np.float64), np.asarray(y_ref, np.float64)
    cap = y_prog.shape[1]
    live = np.arange(cap)[None, :] < np.minimum(np.asarray(count), cap)[:, None]
    rel = (y_prog - y_ref) / np.maximum(np.abs(y_ref), 1e-30)
    return float(np.sqrt(np.mean(rel[live] ** 2)))


def _modules(cell):
    obj = harness.load_module(harness.bench_file(
        cell.root, "objectives", f"{cell.config['objective']}.py"))
    ref = harness.load_module(harness.bench_file(
        cell.root, "reference", f"{cell.traffic['algo']}.py"))
    return obj, ref


def reference_rounds(cell, problem, dim, key, products="highest", fault=None, start=None,
                     rounds=None):
    """The algorithm's plain reference from job key ``key`` (or from the
    program's state ``start``): iterates (K+1, d)."""
    obj, ref = _modules(cell)
    k = int(cell.limits["ref_rounds"]) if rounds is None else rounds
    kw = {} if start is None else {"start": start}
    out = ref.run(obj, problem.data, harness.algo_settings(cell, dim), key, k,
                  products=products, fault=fault, **kw)
    return np.asarray(out["xs"])


def state_rounds(cell, cfg, state) -> int | None:
    """Rounds the kept state has run (its first chunk), or None where its
    trajectories do not hold that many rounds' queries."""
    r = min(int(cell.traffic["chunk"]), int(cell.traffic["rounds"]))
    ok = np.all(np.asarray(state["count"]) == r * cfg.queries_per_round())
    return r if ok else None


def slot_keys(cell, dim, key, state, rounds: int) -> np.ndarray:
    """The query key of each trajectory slot of the kept state (N, cap, 2):
    slot j holds the latest query q < count with q = j mod cap."""
    _, ref = _modules(cell)
    keys = np.asarray(ref.query_keys(key, harness.algo_settings(cell, dim), rounds))
    count = np.asarray(state["count"])
    cap = np.asarray(state["traj_y"]).shape[1]
    j = np.arange(cap)[None, :]
    q = j + cap * ((count[:, None] - 1 - j) // cap)
    q = np.clip(q, 0, keys.shape[1] - 1)
    return np.take_along_axis(keys, q[..., None], axis=1)


def resume_start(rec, rounds: int) -> dict:
    """The reference's start point: the kept state and x_r at its round."""
    start = dict(rec.state)
    start["x"] = np.asarray(rec.result.xs)[rounds]
    return start


def numbers(cell, problem, cfg, jobs, rec, job_key, names, device=None) -> dict:
    """Each number of ``names`` that can be read (``value``), without its
    limit; ``rec`` is the sampled job."""
    import jax

    obj, ref = _modules(cell)
    names = set(names)
    ok = [r for r in jobs if r.result is not None]
    out = {"jobs_lost": float(len(jobs) - len(ok))}
    if not ok:
        return out
    out["queries_off"] = float(queries_off(ok, cfg.queries_per_round()))
    if names & {"f_gap", "f_gap_median"}:
        gaps = obj.f_gaps(problem.data, ok, problem)
        out["f_gap"] = float(np.max(gaps))
        out["f_gap_median"] = float(np.median(gaps))
    if rec is None or rec.result is None:
        return out
    key = job_key(rec.index)
    with jax.default_device(device or jax.devices()[0]):
        if names & {"x_gap", "step_gap"}:
            xs_ref = reference_rounds(cell, problem, cfg.dim, key)
            xs = np.asarray(rec.result.xs)[: xs_ref.shape[0]]
            out["x_gap"] = x_gap(xs, xs_ref)
            out["step_gap"] = step_gap(xs, xs_ref)
        if rec.state is None or not names & {"w_fit_gap", "w_avg_gap", "x_gap_resume", "q_gap"}:
            return out
        r = state_rounds(cell, cfg, rec.state)
        if r is None:
            return out
        st = rec.state
        if names & {"w_fit_gap", "w_avg_gap"}:
            w_ref = ref.w_fits(harness.algo_settings(cell, cfg.dim), key, st["traj_x"],
                               st["traj_y"], st["count"])
            out.update(w_gaps(st["w_local"], st["w_global"], w_ref))
        if "x_gap_resume" in names and np.asarray(rec.result.xs).shape[0] > r + 1:
            xs_ref = reference_rounds(cell, problem, cfg.dim, key, start=resume_start(rec, r),
                                      rounds=1)
            out["x_gap_resume"] = x_gap(np.asarray(rec.result.xs)[r: r + 2], xs_ref)
        if "q_gap" in names and hasattr(obj, "ref_queries"):
            y_ref = obj.ref_queries(problem.data, st["traj_x"],
                                    slot_keys(cell, cfg.dim, key, st, r))
            out["q_gap"] = q_gap(st["traj_y"], y_ref, st["count"])
    return out


def sampled_record(jobs, seed: int):
    """The job the check samples: drawn from the seed among the window's
    first ``harness.KEPT_JOBS`` jobs, which keep their state."""
    kept = [r for r in jobs if r.index < harness.KEPT_JOBS]
    return kept[random.Random(seed).randrange(len(kept))] if kept else None


def check(cell, seed, problem, cfg, jobs, job_key, device=None) -> dict:
    """Numbers beside their limits: ``{name: {"value": v, "limit": l}}``."""
    limits = cell.limits["limits"]
    vals = numbers(cell, problem, cfg, jobs, sampled_record(jobs, seed), job_key, limits,
                   device)
    # a number that could not be read (no job or state came back) fails
    return {name: {"value": vals.get(name, UNREAD), "limit": float(limit)}
            for name, limit in limits.items()}
