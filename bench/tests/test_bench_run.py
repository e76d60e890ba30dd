"""``bench/run.py`` refuses to run anywhere but on the chip."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rf-esa.steady",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_on_a_cpu():
    proc = run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no program" in proc.stderr


def test_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "no-such.cell",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no workload" in proc.stderr
