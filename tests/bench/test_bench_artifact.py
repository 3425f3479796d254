"""The harness keeps one two-tier artifact per configuration, keyed by the
configuration and the seed: a later run of the same seed reads it rather
than building it again, another seed or configuration builds anew."""

import gc
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench import harness  # noqa: E402
from bench.arch import arch_of, program_config  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models.zoo import build_model  # noqa: E402


@pytest.fixture
def built(monkeypatch):
    calls = []
    write = serve.write_artifact

    def counted(*a, **kw):
        calls.append(a[3])
        return write(*a, **kw)

    monkeypatch.setattr(serve, "write_artifact", counted)
    return calls


def test_same_seed_reuses_the_artifact(tmp_path, built):
    conf = bench_tiny.conf(4)
    arch = arch_of(conf)
    model = build_model(program_config(arch))
    plan = serve.analyze_plan(model, SimpleNamespace(**conf["plan"]))

    def artifact(seed, c=conf):
        return harness._artifact(model, arch, c, plan, seed, tmp_path, lambda s: None)

    first = artifact(3)
    assert artifact(3) == first and len(built) == 1
    assert artifact(2**31 + 9) == first and len(built) == 2
    assert artifact(2**31 + 9, {**conf, "residency": "strict"}) == first and len(built) == 3
    assert artifact(2**31 + 9, {**conf, "residency": "strict"}) == first and len(built) == 3
    assert sorted(p.name for p in tmp_path.iterdir()) == [conf["name"], conf["name"] + ".key"]


def test_collections_in_the_window_are_recorded():
    stalls = harness._Stalls()
    gc.collect()
    stalls.on = True
    gc.collect()
    stalls.close()
    gc.collect()
    assert len(stalls.pauses) == 1
    _, seconds, generation = stalls.pauses[0]
    assert seconds >= 0 and generation == 2
    assert "1 collections" in stalls.summary(0.0)
