"""Launcher regression tests: launch/train.py resume-at-end and the
launch/fedzoo.py CLI driven end-to-end on the quadratic objective."""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import fedzoo as fedzoo_launch
from repro.launch import train as train_launch

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_main(monkeypatch, module, argv):
    monkeypatch.setattr(sys, "argv", [f"{module.__name__}"] + argv)
    # the entry point would turn on the checkout's persistent compile cache
    # for the rest of this test process
    monkeypatch.setattr(module, "enable_compile_cache", lambda: None, raising=False)
    module.main()


def test_compile_cache_defaults_to_checkout_dir(monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR: the fixed <checkout>/.jax_cache."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env_dir(tmp_path):
    """Set JAX_COMPILATION_CACHE_DIR: entries land there and the helper sets
    no other directory."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=REPO_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(tmp_path)] * 2
    assert os.listdir(tmp_path), "no cache entry written to JAX_COMPILATION_CACHE_DIR"


TRAIN_ARGS = ["--arch", "qwen1_5_0_5b", "--variant", "smoke", "--steps", "2",
              "--batch-size", "1", "--seq-len", "16", "--ckpt-every", "1"]


def test_train_resume_at_end_regression(monkeypatch, tmp_path, capsys):
    """A restored checkpoint with start >= --steps used to leave `metrics`
    unbound at the trailing save_train_state (NameError)."""
    ckpt = str(tmp_path / "train_ckpt")
    _run_main(monkeypatch, train_launch, TRAIN_ARGS + ["--ckpt-dir", ckpt])
    out = capsys.readouterr().out
    assert "done." in out

    # second invocation restores step 2 >= steps 2: loop body never runs
    _run_main(monkeypatch, train_launch, TRAIN_ARGS + ["--ckpt-dir", ckpt])
    out = capsys.readouterr().out
    assert "restored step 2" in out
    assert "nothing to do" in out


@pytest.mark.parametrize("extra", [
    ["--algo", "fzoos", "--chunk", "5"],
    ["--algo", "fedzo", "--chunk", "0"],
])
def test_fedzoo_cli_smoke_quadratic(monkeypatch, capsys, extra):
    """fedzoo.main() runs end-to-end on the quadratic and the progress table
    includes the FINAL round even when rounds % stride != 0 (seed bug:
    --rounds 7 with stride 1..10 never printed round 7 for e.g. 25/10)."""
    argv = ["--objective", "quadratic", "--dim", "6", "--clients", "4",
            "--rounds", "7", "--local-steps", "2", "--features", "16",
            "--traj-cap", "16", "--lengthscale", "0.5", "--gp-noise", "1e-5",
            "--gamma-mode", "inv_t"] + extra
    _run_main(monkeypatch, fedzoo_launch, argv)
    out = capsys.readouterr().out
    assert "F(x_0)" in out
    assert "round    7" in out  # final round always shown


def test_launch_common_config_from_args_round_trip():
    """The shared flag builder (launch/common.py) maps every flag onto its
    AlgoConfig field -- the single source the launcher AND the benchmark
    configs go through, so there is no drift surface left."""
    import argparse

    from repro.launch import common

    ap = argparse.ArgumentParser()
    common.add_algo_flags(ap)
    common.add_engine_flags(ap)
    args = ap.parse_args([
        "--algo", "fzoos", "--local-steps", "3", "--eta", "0.02", "--q", "4",
        "--features", "32", "--traj-cap", "24", "--lengthscale", "0.7",
        "--gp-noise", "1e-4", "--gamma-mode", "const", "--gamma-const", "0.5",
        "--no-defer-repair", "--eval-every", "4",
    ])
    cfg = common.config_from_args(args, dim=6, n_clients=3)
    assert cfg.name == "fzoos" and cfg.dim == 6 and cfg.n_clients == 3
    assert cfg.local_steps == 3 and cfg.eta == 0.02 and cfg.q == 4
    assert cfg.n_features == 32 and cfg.traj_capacity == 24
    assert cfg.lengthscale == 0.7 and cfg.noise == 1e-4
    assert cfg.gamma_mode == "const" and cfg.gamma_const == 0.5
    assert cfg.defer_repair is False and cfg.use_factor_cache is True
    assert args.eval_every == 4

    # defaults keep the deferred engine on
    cfg2 = common.config_from_args(ap.parse_args([]), dim=4, n_clients=2)
    assert cfg2.defer_repair is True and cfg2.deferred

    # programmatic twin rejects drifted keys loudly
    with pytest.raises(TypeError):
        common.make_config("fzoos", dim=4, n_clients=2, not_a_field=1)


def test_fedzoo_cli_eval_every(monkeypatch, capsys):
    """--eval-every skips the global eval on off-rounds (NaN in the table)
    but always evaluates the final round."""
    argv = ["--objective", "quadratic", "--dim", "4", "--clients", "2",
            "--rounds", "5", "--local-steps", "1", "--features", "8",
            "--traj-cap", "8", "--eval-every", "5", "--chunk", "5"]
    _run_main(monkeypatch, fedzoo_launch, argv)
    out = capsys.readouterr().out
    assert "round    5" in out
    assert "nan" in out  # skipped rounds visible as NaN rows


def test_fedzoo_cli_final_round_not_on_stride(monkeypatch, capsys):
    """rounds=25 -> stride 2: the seed table stopped at 24."""
    argv = ["--objective", "quadratic", "--dim", "4", "--clients", "2",
            "--rounds", "25", "--local-steps", "1", "--algo", "fedzo",
            "--q", "2", "--chunk", "25"]
    _run_main(monkeypatch, fedzoo_launch, argv)
    out = capsys.readouterr().out
    assert "round   24" in out
    assert "round   25" in out


def test_fedzoo_cli_ckpt_flags(monkeypatch, capsys, tmp_path):
    """--ckpt-dir/--ckpt-every/--sync-ckpt wire through to the scan engine
    and the run leaves a complete final checkpoint behind."""
    from repro.checkpoint import latest_step

    ckpt = str(tmp_path / "cli_ckpt")
    argv = ["--objective", "quadratic", "--dim", "4", "--clients", "2",
            "--rounds", "4", "--local-steps", "1", "--algo", "fedzo",
            "--q", "2", "--chunk", "2", "--ckpt-dir", ckpt, "--ckpt-every",
            "2", "--sync-ckpt"]
    _run_main(monkeypatch, fedzoo_launch, argv)
    out = capsys.readouterr().out
    assert "F(x_0)" in out
    assert latest_step(ckpt) == 4


def test_serve_prefill_respects_temperature(monkeypatch):
    """Regression: the FIRST generated token was hard-wired to greedy argmax
    over the prefill logits even with --temperature > 0, so every sampled
    run opened identically.  The prefill step must use the same
    temperature/categorical rule as decode steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve

    batch = 2
    prefill_logits = jnp.asarray(
        [[2.0, 1.8, 1.6, 1.4], [1.0, 2.0, 1.7, 1.5]], jnp.float32)
    decode_logits = jnp.zeros((batch, 4), jnp.float32).at[:, 3].set(5.0)

    monkeypatch.setattr(
        serve, "prefill",
        lambda p, cfg, b, policy, cache_len: (prefill_logits, jnp.zeros((1,))))
    monkeypatch.setattr(
        serve, "decode_step",
        lambda p, cfg, c, t, policy: (decode_logits, c))

    # pick a key whose categorical draw differs from argmax -- on the old
    # greedy-prefill code this test then fails deterministically
    temp = 2.0
    for seed in range(64):
        key = jax.random.PRNGKey(seed)
        _, sub = jax.random.split(key)
        expect = np.asarray(
            jax.random.categorical(sub, prefill_logits / temp))
        if (expect != np.asarray(jnp.argmax(prefill_logits, -1))).any():
            break
    else:  # pragma: no cover - 64 straight argmax draws is ~impossible
        pytest.fail("no differing seed found")

    out, _ = serve.generate(None, None, {}, None, gen_len=3, cache_len=8,
                            temperature=temp, key=key)
    assert out.shape == (batch, 3)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), expect)

    # greedy contract unchanged at temperature 0
    out0, _ = serve.generate(None, None, {}, None, gen_len=2, cache_len=8,
                             temperature=0.0, key=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(
        np.asarray(out0[:, 0]), np.asarray(jnp.argmax(prefill_logits, -1)))
    np.testing.assert_array_equal(np.asarray(out0[:, 1]), [3, 3])

    # the sampling helper itself: argmax at 0, categorical otherwise
    k = jax.random.PRNGKey(7)
    np.testing.assert_array_equal(
        np.asarray(serve.sample_token(prefill_logits, 0.0, k)[:, 0]),
        np.asarray(jnp.argmax(prefill_logits, -1)))
    np.testing.assert_array_equal(
        np.asarray(serve.sample_token(prefill_logits, temp, k)[:, 0]),
        np.asarray(jax.random.categorical(k, prefill_logits / temp)))
