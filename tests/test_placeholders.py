"""Tier-1 placeholders are made on the device (DESIGN.md §8): zeros at full
shape under the leaf's sharding, never host zeros put across — except under
a ``put=`` override, whose function takes host arrays. The counters say
which: ``placeholder_host_bytes`` is 0 on the device path and the whole of
``placeholder_bytes`` under an override."""

import subprocess
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.on_demand import device_zeros, placeholder_tree
from repro.serving import GenerationEngine, cold_start
from repro.utils.tree import flatten_with_paths, tree_bytes

from test_serving import _setup


@pytest.fixture(scope="module")
def app(tmp_path_factory):
    """A reduced mixtral artifact with no hot set: every tier-1 leaf stays a
    placeholder until a request faults it in."""
    return _setup(tmp_path_factory.mktemp("placeholders"),
                  resident_experts=0, hot_vocab_fraction=0.0)


def _tiers(model, res):
    leaves = dict(flatten_with_paths(model.abstract()))
    tier1 = {p: l for p, l in leaves.items() if res.plan.decisions[p].tier != 0}
    tier0 = {p: l for p, l in leaves.items() if res.plan.decisions[p].tier == 0}
    return tier0, tier1


def _generate(server, cfg):
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab_size)
    out, st = GenerationEngine(server, max_seq=24).generate(toks, 4)
    assert st.faulted_bytes > 0
    return np.asarray(out)


def test_placeholders_are_allocated_on_the_device(app, monkeypatch):
    cfg, model, res, outdir = app
    tier0, tier1 = _tiers(model, res)
    given = []
    real_put = jax.device_put

    def spy(x, *a, **k):
        given.append(x)
        return real_put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    server = cold_start(model, outdir, res, mode="after2", compile_warm_set=False)
    monkeypatch.undo()
    # the host arrays put are tier-0's weights, one for each leaf, and no
    # zeros: a tier-1 shape shows up only as often as a tier-0 leaf has it
    # (the expert tables' never)
    hosts = [x for x in given if isinstance(x, np.ndarray)]
    tier0_shapes = Counter((l.shape, np.dtype(l.dtype)) for l in tier0.values())
    assert Counter((x.shape, x.dtype) for x in hosts) == tier0_shapes
    tier1_shapes = {(l.shape, np.dtype(l.dtype)) for l in tier1.values()}
    assert tier1_shapes - set(tier0_shapes)
    assert all(x.any() for x in hosts if (x.shape, x.dtype) in tier1_shapes)
    live = dict(flatten_with_paths(server.tiered.tree()))
    for path, leaf in tier1.items():
        arr = live[path]
        assert isinstance(arr, jax.Array), path
        assert arr.shape == leaf.shape and arr.dtype == leaf.dtype, path
        assert not np.asarray(arr).any(), path
    rep = server.report
    assert rep.placeholder_bytes == tree_bytes(tier1) > 0
    assert rep.placeholder_host_bytes == 0
    assert rep.to_dict()["placeholder_host_bytes"] == 0
    assert rep.bytes_uploaded == tree_bytes(tier0)  # no hot set to preload
    server.close()


def test_a_put_override_is_given_host_zeros(app):
    cfg, model, res, outdir = app
    tier0, tier1 = _tiers(model, res)
    given = []

    def put(host):
        given.append(host)
        return jax.device_put(host)

    with cold_start(model, outdir, res, mode="after2", compile_warm_set=False,
                    put=put) as server:
        # the caller's put is given every leaf as a host array: tier-0's
        # weights, and zeros at the shape of each tier-1 leaf
        assert all(isinstance(x, np.ndarray) for x in given)
        assert len(given) == len(tier0) + len(tier1)
        zeros = Counter((x.shape, x.dtype) for x in given if not x.any())
        assert zeros == Counter((l.shape, np.dtype(l.dtype)) for l in tier1.values())
        rep = server.report
        assert rep.placeholder_host_bytes == rep.placeholder_bytes == tree_bytes(tier1) > 0
        assert rep.bytes_uploaded == tree_bytes(tier0) + tree_bytes(tier1)
        out_put = _generate(server, cfg)
    with cold_start(model, outdir, res, mode="after2", compile_warm_set=False) as server:
        out_device = _generate(server, cfg)
    np.testing.assert_array_equal(out_put, out_device)


def test_placeholder_tree_puts_tier0_only(app):
    cfg, model, res, outdir = app
    tier0, tier1 = _tiers(model, res)
    abstract = model.abstract()
    host0 = {p: np.ones(l.shape, l.dtype) for p, l in tier0.items()}
    dev = jax.devices()[0]
    shardings = {p: jax.sharding.SingleDeviceSharding(dev) for p in tier1}
    put_paths = []

    def put(path, host, leaf):
        put_paths.append(path)
        assert host is host0[path] and leaf.shape == host.shape
        return jax.device_put(host)

    tree = placeholder_tree(abstract, host0, res.plan, put, shardings=shardings)
    assert sorted(put_paths) == sorted(tier0)
    live = dict(flatten_with_paths(tree))
    for path, leaf in tier1.items():
        assert live[path].shape == leaf.shape and live[path].dtype == leaf.dtype
        assert live[path].sharding == shardings[path]
        assert not np.asarray(live[path]).any()
    np.testing.assert_array_equal(np.asarray(live[next(iter(tier0))]), 1)


def test_device_zeros_on_the_default_device():
    z = device_zeros((3, 5), jnp.bfloat16)
    assert z.shape == (3, 5) and z.dtype == jnp.bfloat16
    assert z.sharding.device_set == {jax.devices()[0]}
    assert not np.asarray(z).any()


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, tempfile
sys.path.insert(0, "src")
import jax, numpy as np
assert jax.device_count() == 4, jax.device_count()
from repro.configs import get_reduced
from repro.core import DeploymentProfile, analyze, build_artifact
from repro.launch.mesh import make_debug_mesh
from repro.models.zoo import build_model
from repro.serving import cold_start
from repro.sharding.rules import param_shardings, spec_shard_divisor
from repro.utils.tree import flatten_with_paths

cfg = get_reduced("llama-3.2-vision-90b")
model = build_model(cfg)
profile = DeploymentProfile(resident_experts=0, hot_vocab_fraction=0.0,
                            min_tier1_bytes=1024, vocab_row_group=128)
res = analyze(model, profile, trace_B=1, trace_S=16)
outdir = tempfile.mkdtemp()
build_artifact(model.init(jax.random.PRNGKey(0)), res, outdir)
mesh = make_debug_mesh(2, 2)
want = dict(flatten_with_paths(param_shardings(
    model.logical_axes(), model.abstract(), mesh, fsdp=bool(getattr(cfg, "fsdp", True)))))
decisions = res.plan.decisions
tier1 = [p for p, d in decisions.items() if d.tier != 0]


def check(arr, path, zeros):
    sh = want[path]
    assert arr.sharding == sh, (path, arr.sharding, sh)
    div = spec_shard_divisor(sh.spec, mesh)
    shards = arr.addressable_shards
    assert len(shards) == 4, path
    assert all(s.data.size == arr.size // div for s in shards), (path, div)
    if zeros:
        assert not np.asarray(arr).any(), path
    return div


with cold_start(model, outdir, res, mode="after2", compile_warm_set=False,
                mesh=mesh) as server:
    rep = server.report
    assert rep.placeholder_host_bytes == 0 < rep.placeholder_bytes, rep.to_dict()
    tiered = server.tiered
    divs = {p: check(tiered.leaf(p), p, zeros=True) for p in tier1}
    assert any(d > 1 for d in divs.values()), divs
    # a whole-leaf unit, split over the mesh: fault it in, then evict it
    path = next(p for p in tier1 if decisions[p].granularity == "leaf" and divs[p] > 1)
    key = decisions[path].units[0].key
    assert tiered.ensure([key]) > 0
    check(tiered.leaf(path), path, zeros=False)
    assert np.asarray(tiered.leaf(path)).any(), path
    assert tiered.evict([key]) > 0 and not tiered.is_resident(key)
    check(tiered.leaf(path), path, zeros=True)
print("MESH PLACEHOLDERS OK", path, sum(d > 1 for d in divs.values()), len(divs))
"""


def test_mesh_placeholders_take_the_leaf_sharding():
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                       capture_output=True, text=True, timeout=300, cwd=".")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "MESH PLACEHOLDERS OK" in r.stdout
