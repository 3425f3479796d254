"""The faults of ``test_bench_faults`` on the dense toy, the path of the
dense cell (``mistral_large.steady_chat``): a decode step that returns its
cache unchanged, or a token altered where it is produced, turns
``correct`` false; the same run unbroken is correct."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from repro.serving.engine import GenerationEngine  # noqa: E402
from test_bench_faults import state_unchanged, token_altered  # noqa: E402


@pytest.mark.parametrize("fault", [None, state_unchanged, token_altered])
def test_fault_makes_dense_correct_false(tmp_path, monkeypatch, fault):
    if fault is not None:
        monkeypatch.setattr(GenerationEngine, "decode_once", fault)
    line = bench_tiny.run(tmp_path, 0, 13, seconds=5.0)
    assert line["checks"]["tokens_compared"]["value"] > 0
    assert line["correct"] == (fault is None), line["checks"]
