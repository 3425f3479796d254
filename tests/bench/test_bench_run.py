"""``bench/run.py`` refuses to measure anywhere but on a TPU, and prints no
result where it cannot run."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "mixtral.cold_burst", "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_naming_the_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
