"""Tiny variants of the benchmark's cells, for tests on the CPU.

``make_root`` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
checkout and shrinks every configuration and traffic mix there (widths,
clients, rounds), so the harness's own functions run a whole cell in seconds.
The limits stay the cells' own."""

from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(REPO, "src"))

QUADRATIC = {"dim": 48, "n_clients": 4, "noise_std": 0.01 / 48}
LM = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
      "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 512, "n_clients": 3,
      "tokens_per_seq": 8}
ALGO = {"traj_capacity": 16, "n_features": 32, "active_candidates": 12, "local_steps": 3}
TRAFFIC = {"rounds": 6, "chunk": 3}


def _edit(path: str, fn) -> None:
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    def shrink(c):
        c.update(QUADRATIC if c["objective"] == "quadratic" else LM)
        c["algo"].update(ALGO)

    for name in os.listdir(os.path.join(root, "bench", "configs")):
        _edit(os.path.join(root, "bench", "configs", name), shrink)
    for name in os.listdir(os.path.join(root, "bench", "traffic")):
        _edit(os.path.join(root, "bench", "traffic", name), lambda t: t.update(TRAFFIC))
    return root


def cell_names() -> list[str]:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]
