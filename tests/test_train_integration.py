"""End-to-end integration: the train driver, kill/restart recovery, and
LM data pipeline determinism."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH="src")
ENV.pop("XLA_FLAGS", None)


def _run(args, timeout=900):
    r = subprocess.run([sys.executable, "-m", "repro.launch.train"] + args,
                       capture_output=True, text=True, env=ENV,
                       cwd="/root/repo", timeout=timeout)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    return r.stdout


def _final_loss(out):
    lines = [l for l in out.splitlines() if "loss=" in l]
    return float(lines[-1].split("loss=")[1].split()[0])


def test_gnn_driver_runs():
    out = _run(["--arch", "graphsage", "--dataset", "amazon", "--steps", "6",
                "--batch", "16", "--fanouts", "4,2", "--log-every", "3"])
    assert "steps in" in out
    assert np.isfinite(_final_loss(out))


def test_lm_driver_crash_recovery(tmp_path):
    """Kill-and-restart: run A trains 8 steps with checkpoints; run B trains
    4 steps then 'crashes'; run C auto-resumes and must land on run A's
    exact final loss (batches are pure functions of the step counter)."""
    common = ["--arch", "qwen2-0.5b", "--reduced", "--batch", "4",
              "--seq-len", "32", "--log-every", "4", "--ckpt-every", "4"]
    full = _run(common + ["--steps", "8",
                          "--ckpt-dir", str(tmp_path / "a")])
    _run(common + ["--steps", "4", "--ckpt-dir", str(tmp_path / "b")])
    resumed = _run(common + ["--steps", "8",
                             "--ckpt-dir", str(tmp_path / "b")])
    assert "resumed from step 4" in resumed
    assert abs(_final_loss(full) - _final_loss(resumed)) < 1e-3


def test_gnn_driver_multidevice_resume(tmp_path):
    out1 = _run(["--arch", "graphsage", "--dataset", "reddit", "--steps", "4",
                 "--batch", "8", "--fanouts", "3,2", "--devices", "2",
                 "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"])
    out2 = _run(["--arch", "graphsage", "--dataset", "reddit", "--steps", "8",
                 "--batch", "8", "--fanouts", "3,2", "--devices", "2",
                 "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"])
    assert "resumed from step 4" in out2


def test_train_cli_refuses_mesh_beyond_devices():
    """A mesh larger than the devices the platform has fails loudly
    instead of running on fewer."""
    r = subprocess.run([sys.executable, "-m", "repro.launch.train",
                        "--arch", "graphsage", "--devices", "2",
                        "--mesh", "4x1", "--steps", "1"],
                       capture_output=True, text=True, env=ENV,
                       cwd=REPO, timeout=300)
    assert r.returncode != 0
    assert "needs 4 devices; cpu has 2" in r.stderr


# ---------------------------------------------------------------------------
# the persistent compilation cache's location
# ---------------------------------------------------------------------------

def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory of
    its own, and the entries land in the given one."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()\n")
    env = dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path)]
    assert any(name.endswith("-cache") for name in os.listdir(tmp_path))
