#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... --control-seeds 1,2,3

For each seed one job of the cell runs through the program at the cell's own
size and is compared as a run compares its window (``check.numbers``): the
lower readings.  For each control seed the same comparison is made with
something else put in the program's place, each the upper reading of the
numbers it fails:

* ``control``        -- the plain reference with its products in three bf16
  passes (what a TPU computes at ``high``, the precision below the
  configuration's f32 at highest); for a bf16 model's answers, the reference
  with both operands of every matrix product in fp8 (``objectives/<kind>.py``);
* ``half_batch``     -- the reference whose server averages half the clients;
* ``w_unaveraged``   -- the reference whose server leaves its RFF weights at
  zero (``w_avg_gap`` reads 1 by definition);
* ``answer_altered`` -- the program's reported F of one round off by 1%;
* ``unchanged``      -- a round that returns its state unchanged reads
  x_gap = 1 by definition and needs no run.

``witness`` is no fault: the FZooS reference ranking its candidates by the
exact correction term, against the reference as it is: how far the float32
ties of the score alone move the iterate.

One JSON line per seed goes to standard output.  Needs the chip(s) of the
cell, as a run does; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def altered(rec, r: int):
    """``rec`` with the reported F of round ``r`` off by 1%."""
    f = rec.result.f_values.copy()
    f[r] *= 1.01
    return dataclasses.replace(rec, result=rec.result._replace(f_values=f))


ALL = ("jobs_lost", "queries_off", "f_gap", "f_gap_median", "x_gap", "step_gap",
       "w_fit_gap", "w_avg_gap", "x_gap_resume", "q_gap")


def one_seed(cell, seed, devices, control: bool, rounds: bool) -> dict:
    import jax

    import harness
    import check

    t0 = time.perf_counter()
    runner = harness.Runner(cell, seed, devices)
    rec = runner.run_job(0, keep_state=True)
    if rec.state is not None:
        rec.state = {k: np.asarray(v) for k, v in rec.state.items()}
    p, cfg, key = runner.problem, runner.cfg, runner.job_key(0)
    out = {"seed": seed, "job_s": rec.end - rec.start, "error": rec.error}
    out["program"] = check.numbers(cell, p, cfg, [rec], rec, runner.job_key, ALL, devices[0])
    if not control:
        out["seconds"] = time.perf_counter() - t0
        return out
    obj = harness.load_module(harness.bench_file(
        cell.root, "objectives", f"{cell.config['objective']}.py"))
    ref = harness.load_module(harness.bench_file(
        cell.root, "reference", f"{cell.traffic['algo']}.py"))
    for n in ("control", "half_batch", "w_unaveraged", "witness"):
        out[n] = {}
    with jax.default_device(devices[0]):
        if rounds:
            xs = {"reference": check.reference_rounds(cell, p, cfg.dim, key)}
            k = xs["reference"].shape[0]
            xs["program"] = np.asarray(rec.result.xs)[:k]
            xs["control"] = check.reference_rounds(cell, p, cfg.dim, key, products="bf16x3")
            xs["half_batch"] = check.reference_rounds(cell, p, cfg.dim, key, fault="half_batch")
            if cell.traffic["algo"] == "fzoos":
                xs["witness"] = check.reference_rounds(cell, p, cfg.dim, key, fault="exact_rank")
            fs = {n: obj.ref_global_value(p.data, v) for n, v in xs.items()}
            for n in xs:
                if n == "reference":
                    continue
                out[n]["x_gap_rounds"] = [check.x_gap(xs[n][: r + 1], xs["reference"][: r + 1])
                                          for r in range(1, k)]
                out[n]["x_gap"] = check.x_gap(xs[n], xs["reference"])
                out[n]["step_gap"] = check.step_gap(xs[n], xs["reference"])
                out[n]["descent_gap"] = check.descent_gap(fs[n], fs["reference"])
        r = check.state_rounds(cell, cfg, rec.state) if rec.state is not None else None
        if r is not None and cell.traffic["algo"] == "fzoos":
            st, s = rec.state, harness.algo_settings(cell, cfg.dim)
            w_ref = np.asarray(ref.w_fits(s, key, st["traj_x"], st["traj_y"], st["count"]))
            w_ctl = np.asarray(ref.w_fits(s, key, st["traj_x"], st["traj_y"], st["count"],
                                          products="bf16x3"))
            out["control"].update(check.w_gaps(w_ctl, np.broadcast_to(w_ctl.mean(0), w_ctl.shape),
                                               w_ref))
            half = np.broadcast_to(w_ref[: w_ref.shape[0] // 2].mean(0), w_ref.shape)
            out["half_batch"]["w_avg_gap"] = check.w_gaps(w_ref, half, w_ref)["w_avg_gap"]
            out["w_unaveraged"]["w_avg_gap"] = check.w_gaps(w_ref, 0 * w_ref, w_ref)["w_avg_gap"]
            if np.asarray(rec.result.xs).shape[0] > r + 1:
                start = check.resume_start(rec, r)
                got = {n: check.reference_rounds(cell, p, cfg.dim, key, start=start, rounds=1,
                                                 **kw)
                       for n, kw in (("reference", {}), ("control", {"products": "bf16x3"}),
                                     ("half_batch", {"fault": "half_batch"}),
                                     ("w_unaveraged", {"fault": "w_unaveraged"}),
                                     ("witness", {"fault": "exact_rank"}))}
                for n, v in got.items():
                    if n != "reference":
                        out[n]["x_gap_resume"] = check.x_gap(v, got["reference"])
            if hasattr(obj, "ref_queries"):
                keys = check.slot_keys(cell, cfg.dim, key, st, r)
                y_ref = obj.ref_queries(p.data, st["traj_x"], keys)
                y_ctl = obj.ref_queries(p.data, st["traj_x"], keys, quantize=obj.fp8)
                out["control"]["q_gap"] = check.q_gap(y_ctl, y_ref, st["count"])
    if hasattr(obj, "fp8"):
        g = obj.f_gaps(p.data, [rec], p, quantize=obj.fp8)
        out["control"].update(f_gap=float(np.max(g)), f_gap_median=float(np.median(g)))
    g = obj.f_gaps(p.data, [altered(rec, 1)], p)
    out["answer_altered"] = {"f_gap": float(np.max(g)), "f_gap_median": float(np.median(g))}
    out["unchanged"] = {"x_gap": 1.0, "step_gap": 1.0, "descent_gap": 1.0}
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    ap.add_argument("--no-rounds", action="store_true",
                    help="no replays of the first rounds for the control and faults")
    args = ap.parse_args(argv)

    import harness

    harness.keep_logs_in_checkout()
    cell = harness.load_cell(args.workload)
    devices = harness.require_tpu(cell.chips)
    harness.enable_compile_cache(cell.root)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for s in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(one_seed(cell, s, devices, s in ctl, not args.no_rounds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
