"""The whole run with the timed path broken underneath, on a toy mixture
of experts served from its two-tier artifact: each fault the serving
cells can have turns ``correct`` false, and the same run unbroken is
correct. (Dropping half a batch or the exchange between chips are faults
of training and of several chips; these cells have neither.)"""

import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from repro.serving.engine import GenerationEngine  # noqa: E402

ORIGINAL = GenerationEngine.decode_once


def state_unchanged(self, decode_fn, caches, dbatch, stats, **kw):
    """A decode step that hands back the cache it was given."""
    logits, _, keys = ORIGINAL(self, decode_fn, caches, dbatch, stats, **kw)
    return logits, caches, keys


def token_altered(self, decode_fn, caches, dbatch, stats, **kw):
    """A decode step whose token is not the model's: the logits move by one
    id where they are produced."""
    logits, new, keys = ORIGINAL(self, decode_fn, caches, dbatch, stats, **kw)
    return jnp.roll(logits, 1, axis=-1), new, keys


@pytest.mark.parametrize("fault", [None, state_unchanged, token_altered])
def test_fault_makes_correct_false(tmp_path, monkeypatch, fault):
    if fault is not None:
        monkeypatch.setattr(GenerationEngine, "decode_once", fault)
    line = bench_tiny.run(tmp_path, 4, 11, seconds=6.0)
    assert line["checks"]["tokens_compared"]["value"] > 0
    assert line["correct"] == (fault is None), line["checks"]
