"""The program's own spans (``repro.utils.spans``), windowed for the
per-layer readers in ``bench/metrics``.

The window runs for the window's length from its opening on the span
clock (``time.perf_counter``): in a cold cell from the in-window cold
start's start (``ColdStartReport.t_start``), since the window opens with
it and set-up runs no fault and no step; in a warm cell, which has no cold
start, from the harness's own mark (``Record.t_open``), so the steps that
warmed the replica in set-up stay out. A cold report without ``t_start``,
a program without the recorder, or a ring that has dropped spans of the
window reads nothing (None). A span that did not occur in the window
reads 0."""

from __future__ import annotations

import importlib
import statistics
from typing import Optional

FAULT_SPANS = ("repro.engine.row_ensure", "repro.engine.expert_ensure")


def window(rec) -> Optional[list]:
    """The span records that started in the window, or None."""
    t0 = rec.cold.get("t_start") if rec.cold else rec.t_open
    if t0 is None:
        return None
    try:
        spans = importlib.import_module("repro.utils.spans")
    except ImportError:  # a program that records no spans
        return None
    return spans.RECORDER.between(t0, t0 + rec.seconds)


def seconds(rec, name: str, source: str) -> Optional[float]:
    """Summed seconds of the window's spans ``name`` of ``source``."""
    recs = window(rec)
    if recs is None:
        return None
    return sum(r.end - r.start for r in recs if r.name == name and r.source == source)


def decode_steps(rec) -> Optional[list[tuple[float, float, float]]]:
    """Per scheduler step (``repro.sched.step``) in the window that ran a
    decode: its wall seconds, the host's wait on the decode's outputs
    (``repro.engine.decode_wait``) and its tier-1 fault time (the engine's
    ensures, ``FAULT_SPANS``), each summed over the spans inside it."""
    recs = window(rec)
    if recs is None:
        return None
    parent = {r.seq: r.parent for r in recs}
    steps = {r.seq: [r.end - r.start, 0.0, 0.0, False] for r in recs
             if r.name == "repro.sched.step"}
    for r in recs:
        if r.name not in FAULT_SPANS and r.name not in ("repro.engine.decode_wait",
                                                        "repro.engine.decode_dispatch"):
            continue
        p = r.parent
        while p is not None and p not in steps:
            p = parent.get(p)
        if p is None:
            continue
        step = steps[p]
        if r.name == "repro.engine.decode_dispatch":
            step[3] = True
        elif r.name == "repro.engine.decode_wait":
            step[1] += r.end - r.start
        else:
            step[2] += r.end - r.start
    return [(wall, wait, fault) for wall, wait, fault, decoded in steps.values() if decoded]


def median_ms(values: list) -> float:
    return 1e3 * statistics.median(values) if values else 0.0
