"""The span recorder (``repro.utils.spans``) and the spans the serving path
records: nesting and parents, totals per source, the ring's bound, no
synchronisation, the cold start's upload split, and a fault-in's parts
against the engine's fault time."""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import GenerationEngine, cold_start
from repro.utils import spans
from repro.utils.spans import Recorder, SpanRecord
from repro.utils.tree import flatten_with_paths, tree_bytes

from test_prefetch import UNIT_BYTES, _mini
from test_serving import _setup


def _rec(seq, name, start, end, parent=None, source="", nbytes=0):
    return SpanRecord(seq, name, start, end, parent, source, None, nbytes)


def test_nesting_and_parent():
    r = Recorder()
    with r.span("repro.outer") as outer:
        with r.span("repro.inner", nbytes=3) as inner:
            seen = {}

            def other():
                with r.span("repro.other") as s:
                    seen["s"] = s

            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
            inner.nbytes += 4  # bytes known only inside the block
    by_name = {x.name: x for x in r.records()}
    assert by_name["repro.outer"].parent is None
    assert by_name["repro.inner"].parent == outer.seq
    assert by_name["repro.inner"].nbytes == 7
    # another thread's span has a stack of its own: no parent from this one
    assert seen["s"].parent is None
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert outer.seconds == by_name["repro.outer"].seconds >= inner.seconds


def test_a_raising_span_is_recorded_and_closed():
    r = Recorder()
    with pytest.raises(KeyError):
        with r.span("repro.fails"):
            raise KeyError("x")
    with r.span("repro.after") as after:
        pass
    assert [x.name for x in r.records()] == ["repro.fails", "repro.after"]
    assert after.parent is None  # the failed span left the stack


def test_totals_per_name_and_source():
    r = Recorder()
    for source, nb in (("fault", 10), ("fault", 5), ("preload", 7)):
        with r.span("repro.tier1.read", source=source, nbytes=nb):
            pass
    with r.span("repro.sched.step"):
        pass
    tot = r.totals()
    assert set(tot) == {("repro.tier1.read", "fault"), ("repro.tier1.read", "preload"),
                        ("repro.sched.step", "")}
    n, secs, nb = tot[("repro.tier1.read", "fault")]
    assert (n, nb) == (2, 15) and secs >= 0.0
    assert tot[("repro.tier1.read", "preload")][::2] == (1, 7)
    r.reset()
    assert r.totals() == {} and r.records() == []


def test_ring_is_bounded_and_a_truncated_window_reads_none():
    r = Recorder(ring=4)
    for i in range(10):
        r.add(_rec(i, "repro.x", float(i), i + 0.5))
    assert [x.seq for x in r.records()] == [6, 7, 8, 9]
    assert r.totals()[("repro.x", "")][0] == 10  # totals keep everything
    # the oldest kept record closed at 6.5: a window from 7 on lost nothing
    assert [x.seq for x in r.between(7.0, 9.0)] == [7, 8]
    # a window from 3 on may have lost spans the ring dropped
    assert r.between(3.0, 9.0) is None


def test_names_carry_the_prefix():
    with pytest.raises(ValueError):
        Recorder().span("tier1.read")


def test_a_span_adds_no_synchronisation(monkeypatch):
    """Nothing in a span may wait on the device or pull an array to the
    host: every way to do so raises while the spans run."""
    from jax._src.array import ArrayImpl

    def refuse(*a, **k):
        raise AssertionError("a span waited on the device")

    x = jnp.arange(64.0)
    f = jax.jit(lambda v: v * 2 + 1)
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    monkeypatch.setattr(jax, "device_get", refuse)
    monkeypatch.setattr(ArrayImpl, "block_until_ready", refuse)
    monkeypatch.setattr(ArrayImpl, "__array__", refuse)
    r = Recorder()
    with r.span("repro.engine.decode_dispatch", nbytes=x.nbytes) as s:
        y = f(x)
    with spans.span("repro.test.nested"):
        with spans.span("repro.test.inner", source="fault"):
            y = f(y)
    monkeypatch.undo()
    assert s.seconds >= 0.0 and float(y.sum()) > 0.0


def test_cold_start_upload_parts(tmp_path):
    cfg, model, res, outdir = _setup(tmp_path)
    gc.disable()  # a collection between two spans would open a gap in the sum
    try:
        server = cold_start(model, outdir, res, mode="after2", warm_shapes=((1, 8),))
    finally:
        gc.enable()
    rep = server.report
    parts = rep.tier0_put_s + rep.placeholder_s + rep.preload_s
    assert 0.0 < parts <= rep.upload_s
    assert parts == pytest.approx(rep.upload_s, rel=0.05, abs=0.02)
    leaves = dict(flatten_with_paths(model.abstract()))
    tier1 = sum(tree_bytes(leaf) for path, leaf in leaves.items()
                if res.plan.decisions[path].tier != 0)
    assert rep.placeholder_bytes == tier1 > 0
    moved = sum(e.nbytes for e in server.tiered.stats.events if e.source == "preload")
    assert moved > 0
    tier0 = sum(tree_bytes(leaf) for path, leaf in leaves.items()
                if res.plan.decisions[path].tier == 0)
    # the placeholders are allocated on the device: only tier-0 and the
    # preload's bytes cross from the host
    assert rep.placeholder_host_bytes == 0
    assert rep.bytes_uploaded == tier0 + moved
    d = rep.to_dict()
    assert d["placeholder_bytes"] == tier1 and d["t_start"] == rep.t_start
    assert d["placeholder_host_bytes"] == 0
    assert rep.t_start <= time.perf_counter()


def test_fault_in_parts_within_the_engines_fault_time(tmp_path):
    cfg, model, res, outdir = _setup(tmp_path, resident_experts=0, hot_vocab_fraction=0.0)
    server = cold_start(model, outdir, res, mode="after2", warm_shapes=((1, 8),))
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    _, st = GenerationEngine(server, max_seq=24).generate(toks, 4)
    recs = spans.RECORDER.between(t0, time.perf_counter())
    assert st.faulted_bytes > 0

    def total(names, source=None):
        return sum(r.seconds for r in recs
                   if r.name in names and (source is None or r.source == source))

    parts = total(("repro.tier1.read", "repro.tier1.decode", "repro.tier1.install",
                   "repro.tier1.wait"), "fault")
    assert 0.0 < parts <= st.fault_s
    # one timing feeds both: the engine's fault time is its ensures' spans
    ensures = total(("repro.engine.row_ensure", "repro.engine.expert_ensure"))
    assert ensures == pytest.approx(st.fault_s, rel=1e-9)
    faulted = sum(r.nbytes for r in recs if r.name == "repro.tier1.install")
    assert faulted == st.faulted_bytes


def test_a_wait_on_an_inflight_load_is_a_span(tmp_path):
    tp, data, units = _mini(tmp_path)
    key = units[4].key
    assert tp.claim_for_prefetch(key)

    def finish():
        time.sleep(0.15)
        tp.install_prefetched(key, tp.store.fetch(key))

    t = threading.Thread(target=finish)
    t0 = time.perf_counter()
    t.start()
    moved = tp.ensure([key])
    t.join(timeout=10)
    assert not t.is_alive() and moved == 0
    recs = spans.RECORDER.between(t0, time.perf_counter())
    waits = [r for r in recs if r.name == "repro.tier1.wait"]
    assert len(waits) == 1 and waits[0].source == "fault" and waits[0].seconds > 0.05
    installs = [r for r in recs if r.name == "repro.prefetch.install"]
    assert len(installs) == 1 and installs[0].nbytes == UNIT_BYTES
    assert installs[0].source == "prefetch"
    ev = tp.stats.events[-1]
    assert ev.source == "prefetch" and ev.upload_s == pytest.approx(installs[0].seconds)
    np.testing.assert_array_equal(np.asarray(tp.leaf("emb"))[64:80], data[64:80])
