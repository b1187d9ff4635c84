"""The harness refuses a run it cannot measure: no TPU, or no program."""
import os
import shutil
import subprocess
import sys

import _paths  # noqa: F401

RUN = os.path.join(_paths.BENCH, "run.py")


def _run(cwd_root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd_root, "benchmarks", "chip",
                                      "run.py"),
         "--workload", "phi4-mini-3.8b.chat", "--seed", "3", "--seconds",
         "1", "--trace", "0"], cwd=cwd_root, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_refuses_without_a_tpu():
    p = _run(_paths.ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_paths.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    _no_result(p)
    assert "src/repro" in p.stderr
