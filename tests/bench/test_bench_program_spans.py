"""The per-layer readers of the program's own spans, on a synthetic record
and recorder; the trace reduction given those spans; and the profile,
where the program's spans sit beside the harness's."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness, trace  # noqa: E402
from repro.utils import spans  # noqa: E402
from repro.utils.spans import Recorder, SpanRecord  # noqa: E402

T0 = 1000.0  # the cold start's start on the span clock
COLD = {"upload_s": 9.0, "tier0_put_s": 1.0, "placeholder_s": 5.0, "placeholder_bytes": 5 << 30,
        "preload_s": 3.0, "t_start": T0}


def _record(cold=COLD, seconds=50.0):
    return harness.Record(arch=None, mix={}, seconds=seconds, cold=cold)


def _read(name, rec):
    return harness.load_reader(name)(rec)


def _span(seq, name, start, end, parent=None, source="", nbytes=0):
    return SpanRecord(seq, name, T0 + start, T0 + end, parent, source, None, nbytes)


def _recorder(records):
    r = Recorder()
    for x in records:
        r.add(x)
    return r


# a cold start's preload, then one step that faults in (before the window
# closes), one step past the window, and three decode steps
SPANS = [
    _span(0, "repro.cold.preload", 0.0, 3.0),
    _span(1, "repro.tier1.read", 0.5, 2.0, parent=0, source="preload"),
    _span(2, "repro.sched.step", 10.0, 20.0),                    # admission + decode
    _span(3, "repro.sched.prefill", 10.0, 19.0, parent=2),
    _span(4, "repro.engine.expert_ensure", 10.5, 18.5, parent=3),
    _span(5, "repro.tier1.read", 10.5, 16.5, parent=4, source="fault"),
    _span(6, "repro.tier1.decode", 16.5, 17.0, parent=4, source="fault"),
    _span(7, "repro.tier1.install", 17.0, 18.0, parent=4, source="fault"),
    _span(8, "repro.tier1.wait", 18.0, 18.25, parent=4, source="fault"),
    _span(9, "repro.engine.decode_dispatch", 19.0, 19.1, parent=2),
    _span(10, "repro.engine.decode_wait", 19.1, 19.9, parent=2),
    _span(11, "repro.sched.step", 21.0, 21.1),                   # decode steps
    _span(12, "repro.engine.decode_dispatch", 21.0, 21.01, parent=11),
    _span(13, "repro.engine.decode_wait", 21.01, 21.07, parent=11),
    _span(14, "repro.sched.step", 22.0, 22.2),
    _span(15, "repro.engine.row_ensure", 22.0, 22.05, parent=14),
    _span(16, "repro.engine.decode_dispatch", 22.05, 22.06, parent=14),
    _span(17, "repro.engine.decode_wait", 22.06, 22.16, parent=14),
    _span(18, "repro.sched.step", 23.0, 23.09),
    _span(19, "repro.engine.decode_dispatch", 23.0, 23.01, parent=18),
    _span(20, "repro.engine.decode_wait", 23.01, 23.05, parent=18),
    _span(21, "repro.sched.step", 24.0, 24.5),                   # admission only
    _span(22, "repro.prefetch.read", 24.0, 24.4, source="prefetch"),
    _span(23, "repro.tier1.read", 60.0, 61.0, source="fault"),   # after the window
    _span(24, "repro.sched.step", 60.0, 60.2),
    _span(25, "repro.engine.decode_dispatch", 60.0, 60.1, parent=24),
    _span(-1, "repro.tier1.read", -5.0, -4.0, source="fault"),   # before it
]


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(spans, "RECORDER", _recorder(sorted(SPANS, key=lambda r: r.end)))


def test_cold_readers():
    rec = _record()
    assert _read("cold_tier0_put_s", rec) == 1.0
    assert _read("cold_placeholder_s", rec) == 5.0
    assert _read("cold_placeholder_bytes", rec) == float(5 << 30)
    assert _read("cold_preload_s", rec) == 3.0
    # a warm cell, or a program whose report lacks the split, reads nothing
    for name in ("cold_tier0_put_s", "cold_placeholder_s", "cold_placeholder_bytes",
                 "cold_preload_s"):
        assert _read(name, _record(cold=None)) is None
        assert _read(name, _record(cold={"upload_s": 9.0, "read_s": 1.0})) is None


def test_tier1_readers_keep_demand_faults_in_the_window(recorded):
    rec = _record()
    assert _read("tier1_read_s", rec) == pytest.approx(6.0)
    assert _read("tier1_decode_s", rec) == pytest.approx(0.5)
    assert _read("tier1_install_s", rec) == pytest.approx(1.0)
    assert _read("tier1_wait_s", rec) == pytest.approx(0.25)
    # a longer window takes the fault past the close in too
    assert _read("tier1_read_s", _record(seconds=70.0)) == pytest.approx(7.0)


def test_decode_step_readers(recorded):
    rec = _record()
    # decode steps (wall, wait, fault): (10, 0.8, 8), (0.1, 0.06, 0),
    # (0.2, 0.1, 0.05), (0.09, 0.04, 0); the admission-only step is none
    assert _read("decode_wait_ms", rec) == pytest.approx(1e3 * (0.06 + 0.1) / 2)
    host = sorted([10 - 0.8 - 8, 0.1 - 0.06, 0.2 - 0.1 - 0.05, 0.09 - 0.04])
    assert _read("decode_host_ms", rec) == pytest.approx(1e3 * (host[1] + host[2]) / 2)


def test_spans_that_did_not_occur_read_zero(monkeypatch):
    monkeypatch.setattr(spans, "RECORDER", Recorder())
    rec = _record()
    for name in ("tier1_read_s", "tier1_decode_s", "tier1_install_s", "tier1_wait_s",
                 "decode_wait_ms", "decode_host_ms"):
        assert _read(name, rec) == 0.0


def test_no_window_or_no_recorder_reads_nothing(recorded, monkeypatch):
    names = ("tier1_read_s", "tier1_decode_s", "tier1_install_s", "tier1_wait_s",
             "decode_wait_ms", "decode_host_ms")
    for cold in (None, {"upload_s": 9.0}):  # warm cell; a report with no span clock
        for name in names:
            assert _read(name, _record(cold=cold)) is None
    # a ring that dropped spans of the window
    small = Recorder(ring=4)
    for x in sorted(SPANS, key=lambda r: r.end):
        small.add(x)
    monkeypatch.setattr(spans, "RECORDER", small)
    for name in names:
        assert _read(name, _record()) is None
    # a program without the recorder
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    for name in names:
        assert _read(name, _record()) is None


# -- the trace reduction ------------------------------------------------------

HAND = {
    "device": {"/device:TPU:0": {"ops": [["fusion.1", 0, 10], ["copy.2", 90, 10]],
                                 "modules": [["jit_step(1)", 0, 10]]}},
    "spans": [["bench.fault", 5, 90], ["repro.tier1.read", 10, 60],
              ["repro.tier1.install", 70, 25]],
}


def test_a_program_span_names_the_gap_inside_a_harness_span():
    """Given the program's spans (``load`` keeps them beside the
    harness's), the reduction names a gap by the innermost of them and
    moves nothing else."""
    r = trace.reduce(HAND, (0, 100))
    # the idle gap [10, 90] has its midpoint inside the program's read
    assert r["idle_gaps"] == [["repro.tier1.read", pytest.approx(80e-9)]]
    old = trace.reduce({**HAND, "spans": HAND["spans"][:1]}, (0, 100))
    assert old["idle_gaps"] == [["bench.fault", pytest.approx(80e-9)]]
    assert (r["busy_s"], r["window_s"], r["device_ops"]) == \
        (old["busy_s"], old["window_s"], old["device_ops"])


def test_recorded_slice_reduces_as_before_with_program_spans():
    """The recorded v5e slice with program spans laid over it: only the
    labels can move; busy time, the window and the top operations stay."""
    ev = trace.load_events(str(HERE / "tpu_v5e_steady_slice.json.gz"))
    before = trace.reduce(ev, (0, 400_000_000))
    assert before["busy_s"] == pytest.approx(0.174564196, rel=1e-9)
    extra = [["repro.sched.step", s, d] for n, s, d in ev["spans"] if n == "bench.step"]
    extra += [["repro.engine.decode_wait", s, d // 2] for n, s, d in ev["spans"]
              if n == "bench.decode"]
    assert extra
    after = trace.reduce({**ev, "spans": ev["spans"] + extra}, (0, 400_000_000))
    assert after["busy_s"] == before["busy_s"] and after["window_s"] == before["window_s"]
    assert after["device_ops"] == before["device_ops"]
    assert after["modules_s"] == before["modules_s"]


def test_program_spans_reach_the_profile_beside_the_harness_spans(tmp_path):
    """A profile taken here (CPU): the program's spans land in it as
    annotations of their own names, beside the harness's; ``load`` keeps
    both, the harness's ``bench.*`` and the program's ``repro.*``, and
    nothing else, so a gap is named by the innermost of them."""
    from jax.profiler import ProfileData

    f = jax.jit(lambda v: v * 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.fault"):
            with spans.span("repro.tier1.read", source="fault"):
                f(jnp.ones(8)).block_until_ready()
        with jax.profiler.TraceAnnotation("elsewhere.span"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    raw = {e.name for plane in ProfileData.from_file(path).planes
           if not plane.name.startswith("/device:") for line in plane.lines for e in line.events}
    assert {"bench.fault", "repro.tier1.read", "elsewhere.span"} <= raw
    names = {n for n, _, _ in trace.load(path)["spans"]}
    assert {"bench.fault", "repro.tier1.read"} <= names
    assert "elsewhere.span" not in names
