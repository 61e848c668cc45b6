"""chip_smoke.py off the chip, and the compile-cache helper it shares
with every entry point (xflow_tpu/utils/compile_cache.py).

The smoke itself only means something on a TPU (the driver runs it
there).  Tier-1 checks the two things a CPU can: without a chip it
refuses, and under --rehearsal every phase's code runs at toy size and
the result can never be mistaken for a chip's.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(*argv, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )


def test_no_chip_no_result():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert "no chip found" in proc.stderr
    assert "cpu" in proc.stderr  # says what JAX did find
    assert proc.stdout.strip() == ""  # no result line at all


def test_rehearsal_runs_every_phase_and_never_says_ok():
    # four virtual devices so the mesh phase runs too
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    proc = _run_smoke("--rehearsal", env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # the last line is the driver's contract: the verdict and the device
    # as JAX reports it, nothing else; under --rehearsal never "ok"
    assert verdict == {
        "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    phases = report["phases"]
    assert list(phases) == ["build", "train", "parity", "serve", "mesh"]
    assert phases["train"]["wire"] == "dict"
    assert phases["train"]["last_logloss"] < phases["train"]["first_logloss"]
    assert phases["parity"]["gather_bitwise"] is True
    assert phases["serve"]["errors"] == 0
    assert (
        phases["serve"]["compiles_after_traffic"]
        == phases["serve"]["compiles_after_warm"]
    )
    assert phases["mesh"]["devices"] == 4
    assert phases["mesh"]["wire"] == "compact"
    for name, phase in phases.items():
        assert phase["seconds"] >= phase["compile_seconds"] >= 0, name


def test_a_failed_phase_fails_the_smoke(tmp_path):
    # no g++ on PATH: the build phase cannot rebuild the native parser
    env = dict(os.environ, JAX_PLATFORMS="cpu", PATH=str(tmp_path))
    proc = _run_smoke("--rehearsal", env=env)
    assert proc.returncode != 0
    assert "g++" in proc.stderr
    assert "rehearsal" not in proc.stdout  # no result line


_HELPER = (
    "from xflow_tpu.utils.compile_cache import enable_compile_cache\n"
    "import jax\n"
    "print(enable_compile_cache())\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _helper_dirs(**env_over):
    """What the helper returns (twice) and what JAX ends up with, in a
    fresh process that never initializes a backend."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(env_over)
    proc = subprocess.run(
        [sys.executable, "-c", _HELPER], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_compile_cache_dir_is_placeable_from_outside(tmp_path):
    placed = str(tmp_path / "cc")
    assert _helper_dirs(JAX_COMPILATION_CACHE_DIR=placed) == [placed] * 3


def test_compile_cache_default_is_one_fixed_gitignored_path():
    fixed = os.path.join(REPO, ".jax_cache")
    # same path on two calls and in two processes: it is part of the key
    assert _helper_dirs() == [fixed] * 3
    assert _helper_dirs(JAX_PLATFORMS="tpu,cpu") == [fixed] * 3
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_leaves_a_cpu_pinned_run_alone():
    assert _helper_dirs(JAX_PLATFORMS="cpu") == ["None"] * 3
