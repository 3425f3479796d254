"""``correct`` on a toy cell: the program, served through the scheduler
from the two-tier artifact, passes its check, and the fp8 control, read
on the same prompts and tokens and judged by the same limits, fails it."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
def test_program_passes_and_fp8_control_fails(tmp_path, seed):
    line = bench_tiny.run(tmp_path, 0, seed, control=True)
    checks = line["checks"]
    assert line["correct"], checks
    assert checks["tokens_compared"]["value"] >= checks["tokens_compared"]["limit"]
    assert line["control_correct"] is False, line["control_checks"]
    assert set(line["control_checks"]) == set(checks)
    assert list(line)[-1] == "checks"
