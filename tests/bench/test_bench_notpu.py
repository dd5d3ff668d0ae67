"""bench/run.py makes no run, and prints no result, where it cannot."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "jacobi2d-16k.t1", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0")


def test_exits_nonzero_without_a_tpu():
    r = _run(ROOT, *ARGS)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_exits_nonzero_on_an_unknown_workload():
    r = _run(ROOT, "--workload", "no-such-cell", "--seed", "1",
             "--seconds", "1")
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "unknown workload" in r.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, *ARGS)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no program" in r.stderr
