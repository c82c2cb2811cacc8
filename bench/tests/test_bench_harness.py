"""The harness on the CPU: refusal without a chip, discovery of files by
name, nothing started at import, and a well-formed last line from a tiny
variant of each cell driven through the harness's own functions."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

import tinycells

import check  # noqa: E402  (tinycells puts bench/ on the path)
import harness  # noqa: E402

REPO = tinycells.REPO
CMD = ["bench/run.py", "--workload", "quad-d3072-n64.fzoos", "--seed", "0",
       "--seconds", "10", "--trace", "0"]


def _run_command(cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def test_command_refuses_without_a_tpu():
    p = _run_command(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run_command(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_nothing_starts_at_import():
    """Importing every file of the benchmark initialises no JAX backend and
    describes no topology."""
    code = textwrap.dedent(f"""
        import glob, os, sys
        bench = {os.path.join(REPO, "bench")!r}
        sys.path[:0] = [bench, os.path.join(os.path.dirname(bench), "src")]
        import harness
        files = [f for pat in ("*.py", "metrics/*.py", "objectives/*.py", "reference/*.py")
                 for f in sorted(glob.glob(os.path.join(bench, pat)))]
        for f in files:
            harness.load_module(f)
        from jax._src import xla_bridge
        assert not xla_bridge._backends, sorted(xla_bridge._backends)
        print(len(files))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr
    assert int(p.stdout.strip()) >= 20


def _check_line(out: dict, cell: harness.Cell) -> None:
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert isinstance(out["correct"], bool)
    assert out["attempted"] >= 1 and 0 <= out["failed"] <= out["attempted"]
    want = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert set(out["checks"]) == set(cell.limits["limits"])
    for n in out["checks"].values():
        assert set(n) == {"value", "limit"}
    json.loads(json.dumps(out))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tinycells.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("name", tinycells.cell_names())
def test_tiny_cell_gives_a_well_formed_last_line(tiny_root, name):
    import jax

    cell = harness.load_cell(name, tiny_root)
    out = harness.run(cell, 2**33 + 12345, 0.1, False, time.perf_counter(),
                      jax.devices()[:1], log=lambda m: None)
    _check_line(out, cell)
    # the exact numbers hold at any size
    for n in ("jobs_lost", "queries_off"):
        assert out["checks"][n]["value"] == 0.0
    # every number was read, the kept client state's too
    assert all(n["value"] < check.UNREAD for n in out["checks"].values()), out["checks"]
    if "q_gap" in out["checks"]:  # the answers' noise is replayed from the keys
        assert out["checks"]["q_gap"]["value"] < 1e-3


def test_new_files_are_found_by_name(tiny_root, tmp_path):
    """A configuration, a traffic mix, a cell and a metric added as files
    (and entries of BENCHMARK.json) run with no edit of the harness."""
    import jax

    root = str(tmp_path / "checkout")
    shutil.copytree(tiny_root, root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "quad-d3072-n64.json")) as f:
        cfg = json.load(f)
    cfg.update(name="quad-d32-n2", dim=32, n_clients=2)
    with open(os.path.join(b, "configs", "quad-d32-n2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "fzoos-r4.json"), "w") as f:
        json.dump({"algo": "fzoos", "rounds": 4, "chunk": 2, "eval_every": 1,
                   "target_gap_closed": None, "algo_settings": {}}, f)
    with open(os.path.join(b, "limits", "quad-d32-n2.fzoos-r4.json"), "w") as f:
        json.dump({"ref_rounds": 2, "limits": {"jobs_lost": 0, "queries_off": 0,
                                               "f_gap": 1.0, "x_gap": 10.0}}, f)
    with open(os.path.join(b, "metrics", "jobs_done.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx.ok_jobs))\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "quad-d32-n2", "source": "test", "reduced": [],
                            "file": "bench/configs/quad-d32-n2.json", "why": "test"})
    spec["workloads"].append({"name": "quad-d32-n2.fzoos-r4", "config": "quad-d32-n2",
                              "traffic": "fzoos-r4", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "jobs_done", "unit": "jobs", "better": "higher",
                               "bound": 0.01, "source": "host_clock",
                               "workloads": ["quad-d32-n2.fzoos-r4"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    cell = harness.load_cell("quad-d32-n2.fzoos-r4", root)
    assert cell.config["dim"] == 32 and cell.traffic["rounds"] == 4
    out = harness.run(cell, 7, 0.1, False, time.perf_counter(), jax.devices()[:1],
                      log=lambda m: None)
    assert out["metrics"]["jobs_done"]["value"] == out["attempted"]
    assert out["correct"] is True


def test_unknown_cell_and_missing_file(tiny_root):
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", tiny_root)
    os.remove(os.path.join(tiny_root, "bench", "limits", "quad-d3072-n64.fedzo.json"))
    try:
        with pytest.raises(FileNotFoundError):
            harness.load_cell("quad-d3072-n64.fedzo", tiny_root)
    finally:
        shutil.copy(os.path.join(REPO, "bench", "limits", "quad-d3072-n64.fedzo.json"),
                    os.path.join(tiny_root, "bench", "limits"))


def test_seed_keys_differ_above_32_bits():
    import jax.numpy as jnp

    a, b = harness.seed_key(7), harness.seed_key(2**33 + 7)
    assert not bool(jnp.all(a == b))
    with pytest.raises(ValueError):
        harness.seed_key(-1)
