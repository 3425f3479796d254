"""The trace reduction: busy and idle time, program time by host span, and
idle gaps named by what the host was doing; on hand-made events whose
answer is known, and on a short slice of a recorded TPU v5e trace."""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace  # noqa: E402

HAND = {
    "device": {
        "/device:TPU:0": {
            "ops": [["fusion.1", 10, 20], ["fusion.2", 20, 20], ["copy.3", 60, 10]],
            "modules": [["jit_step(1)", 10, 30], ["jit_graft(2)", 60, 10]],
        },
        "/device:TPU:1": {"ops": [["fusion.1", 0, 50]], "modules": [["jit_step(1)", 0, 50]]},
    },
    "spans": [["bench.decode", 0, 50], ["bench.fault", 40, 20], ["bench.graft", 55, 15]],
}


def test_hand_made_events():
    r = trace.reduce(HAND, (0, 100))
    assert r["chips"] == 2 and r["window_s"] == 100e-9
    # chip 0 busy [10, 40] and [60, 70]; chip 1 [0, 50]: mean 45 ns
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["modules_s"] == {"jit_step(1)": pytest.approx(80e-9),
                              "jit_graft(2)": pytest.approx(10e-9)}
    # a program belongs to the innermost span open at its midpoint
    assert r["in_span"] == {"bench.decode": {"jit_step(1)": (pytest.approx(80e-9), 2)},
                            "bench.graft": {"jit_graft(2)": (pytest.approx(10e-9), 1)}}
    assert r["device_ops"][0] == ("fusion.1", pytest.approx(70e-9))
    # gaps, longest first: chip 1 [50, 100] and chip 0 [70, 100] have no
    # span open at their midpoints; chip 0 [40, 60] is inside the fault
    # span (the decode span closes at 50); chip 0 [0, 10] inside decode
    assert r["idle_gaps"] == [["no host span", pytest.approx(50e-9)],
                              ["no host span", pytest.approx(30e-9)],
                              ["bench.fault", pytest.approx(20e-9)],
                              ["bench.decode", pytest.approx(10e-9)]]


def test_window_defaults_to_the_device_events():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(70e-9)


def test_no_device_events_reduce_to_nothing():
    assert trace.reduce({"device": {}, "spans": []}) == {}


def test_recorded_tpu_trace_slice():
    """0.4 s of mistral-large-123b-3L serving 32 slots on one TPU v5e
    (steady_chat), cut from a recorded trace: a prefill of 1024 tokens
    among masked decode steps."""
    ev = trace.load_events(str(HERE / "tpu_v5e_steady_slice.json.gz"))
    r = trace.reduce(ev, (0, 400_000_000))
    assert r["chips"] == 1 and r["window_s"] == pytest.approx(0.4)
    assert r["busy_s"] == pytest.approx(0.174564196, rel=1e-9)
    seconds, runs = max(r["in_span"]["bench.decode"].values())
    assert runs == 5 and seconds == pytest.approx(0.113442631, rel=1e-9)
    assert list(r["in_span"]["bench.prefill"].values()) == [(pytest.approx(0.061056735), 1)]
    assert r["device_ops"][0][0] == "%while.13"  # the decode's layer loop
    assert r["idle_gaps"][0] == ["no host span", pytest.approx(0.049120868, rel=1e-9)]
    assert all(g <= 0.4 for _, g in r["idle_gaps"])
