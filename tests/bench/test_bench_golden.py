"""The Mistral family read through its adapter gives, bit for bit, what the
harness gave before family code had files of its own: the weight layout,
the work counts at fixed shapes (a window and routed experts among them),
the toy configurations' program weights and reference logits, and
``step_mfu`` on a fixed record. The values in ``golden_mistral.json`` were
recorded from the harness as it stood before the split, on this CPU
backend (the reference's float32 logits are bitwise the same on one core
and on eight)."""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE))

import bench_tiny  # noqa: E402
from bench import harness, weights, work  # noqa: E402
from bench.arch import arch_of, family_of, load_config, load_reference, program_config  # noqa: E402

GOLDEN = json.loads((HERE / "golden_mistral.json").read_text())
SEED = 2**31 + 77
PREFILLS = [([128], 0), ([512, 512, 1024], 7), ([2048] * 5, 8), ([8, 16, 16], 3)]
DECODES = [([5, 2], 0), ([1, 9, 130, 2048, 2560, 17, 700, 33], 11), (list(range(1, 17)), 5)]
# (kind, start, end, lengths, fault_s, fault_bytes, experts)
STEPS = [("prefill", 0.0, 0.9, [512, 512, 1024], 0.25, 10, 7),
         ("decode", 1.0, 1.07, [513, 513, 1025, 30], 0.0, 0, 8),
         ("decode", 1.1, 1.2, [514, 514, 1026, 31, 2048], 0.01, 5, 5),
         ("prefill", 1.3, 1.6, [128], 0.0, 0, 2)]


def _conf(name: str) -> dict:
    if name.startswith("toy-"):
        conf = bench_tiny.conf(int(name[4]))
        return {**conf, "sliding_window": 6} if name.endswith("-window") else conf
    return load_config(name)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_leaf_specs(name):
    arch = arch_of(_conf(name))
    got = {k: [list(s), kind, scale] for k, (s, kind, scale) in
           family_of(arch).leaf_specs(arch).items()}
    assert got == GOLDEN[name]["leaf_specs"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_work_counts(name):
    arch = arch_of(_conf(name))
    assert [list(work.prefill(arch, L, x)) for L, x in PREFILLS] == GOLDEN[name]["prefill"]
    assert [list(work.decode(arch, L, x)) for L, x in DECODES] == GOLDEN[name]["decode"]


@pytest.mark.parametrize("name", [n for n in sorted(GOLDEN) if "step_mfu" in GOLDEN[n]])
def test_step_mfu_reads_the_same(name):
    rec = harness.Record(arch=arch_of(_conf(name)), mix={}, seconds=51.0,
                         steps=[harness.Step(*s) for s in STEPS], device_kind="TPU v5 lite")
    assert harness.load_reader("step_mfu")(rec) == GOLDEN[name]["step_mfu"]


@pytest.mark.parametrize("name", [n for n in sorted(GOLDEN) if "program_params" in GOLDEN[n]])
def test_program_params(name):
    from repro.models.zoo import build_model
    from repro.utils.tree import flatten_with_paths

    arch = arch_of(_conf(name))
    params = weights.program_params(arch, build_model(program_config(arch)), SEED)
    flat = sorted(flatten_with_paths(params), key=lambda p: p[0])
    assert [p for p, _ in flat] == GOLDEN[name]["program_paths"]
    assert _digest([np.asarray(a) for _, a in flat]) == GOLDEN[name]["program_params"]


@pytest.mark.parametrize("name", [n for n in sorted(GOLDEN) if "logits" in GOLDEN[n]])
def test_reference_logits(name):
    conf = _conf(name)
    arch, reference = arch_of(conf), load_reference(conf)
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 1024, 12, dtype=np.int32), rng.integers(0, 1024, 20, dtype=np.int32)]
    first = [len(s) - 6 for s in seqs]
    margins = []
    ref = reference.logits(arch, SEED, seqs, first, pad_to=32, margins=margins)
    low = reference.logits(arch, SEED, seqs, first, pad_to=32, precision="fp8")
    assert _digest(ref) == GOLDEN[name]["logits"]
    assert _digest(margins) == GOLDEN[name]["margins"]
    assert _digest(low) == GOLDEN[name]["logits_fp8"]
