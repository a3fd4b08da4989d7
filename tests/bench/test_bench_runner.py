"""The runner refuses to run without an accelerator, printing no result,
and cannot run from the benchmark's files alone."""

import os
import shutil
import subprocess
import sys

from harness import REPO_ROOT

RUN = ["benchmarks/chip/run.py", "--workload", "ctr-gather-mb1k",
       "--seed", "3000000000", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *RUN], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_no_accelerator_no_result():
    p = _run(REPO_ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(REPO_ROOT / "BENCHMARK.json", tmp_path)
    for sub in ("benchmarks/chip", "tests/bench"):
        shutil.copytree(REPO_ROOT / sub, tmp_path / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
