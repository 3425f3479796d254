"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler writes and keeps only
what the reduction needs, as plain data that ``save_events`` can store as
JSON (the recorded trace the tests read):

  device  {device name: {"ops": [(name, start_ns, dur_ns)],
                         "modules": [(name, start_ns, dur_ns)]}}
  spans   [(name, start_ns, dur_ns)] host annotations named ``bench.*``

``reduce`` turns that into the device's busy time (the union of the
intervals in which an operation ran, per chip, averaged over the chips),
the traced window, each program's device time, the programs that ran
inside each kind of host span (device time and executions), and the
longest idle gaps. A program or a gap belongs to the innermost host span
open at its midpoint.
"""

from __future__ import annotations

import glob
import gzip
import json

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
OP_NAME_CHARS = 160


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: dict = {}
    spans: list = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):].isdigit():
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    rec[key] += [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
            device[name] = rec
        elif not name.startswith("/device:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return {"device": device, "spans": spans}


def save_events(ev: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(ev, f)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a0: int, a1: int, b0: int, b1: int) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def _label(spans, t: int):
    """The innermost host span open at time ``t`` (the shortest of those
    that contain it), or None."""
    best, label = None, None
    for a, b, name in spans:
        if a <= t < b and (best is None or b - a < best):
            best, label = b - a, name
    return label


def reduce(ev: dict, window_ns: tuple[int, int] | None = None, top: int = 10) -> dict:
    """Busy and idle time over ``window_ns`` (default: from the first to
    the last event of any device), per-program device time, per kind of
    ``bench.*`` span the (device seconds, executions) of each program that
    ran in it, the ``top`` device operations by time and the ``top``
    longest idle gaps by host span."""
    devs = {d: r for d, r in ev["device"].items() if r["ops"] or r["modules"]}
    if not devs:
        return {}
    if window_ns is None:
        starts = [s for r in devs.values() for _, s, _ in r["ops"] + r["modules"]]
        ends = [s + d for r in devs.values() for _, s, d in r["ops"] + r["modules"]]
        window_ns = (min(starts), max(ends))
    w0, w1 = window_ns
    spans = sorted((s, s + d, n) for n, s, d in ev["spans"])
    busy_total = 0
    ops_s: dict[str, float] = {}
    modules_s: dict[str, float] = {}
    in_span: dict[str, dict[str, tuple[float, int]]] = {}
    gaps: list[tuple[int, int]] = []
    for r in devs.values():
        ivs = [(max(s, w0), min(s + d, w1)) for _, s, d in r["ops"] if s + d > w0 and s < w1]
        busy = _union(ivs)
        busy_total += sum(e - s for s, e in busy)
        for n, s, d in r["ops"]:
            o = _overlap(s, s + d, w0, w1)
            if o:
                n = n[:OP_NAME_CHARS]  # an op's name is its HLO text; keep its head
                ops_s[n] = ops_s.get(n, 0.0) + o / 1e9
        for n, s, d in r["modules"]:
            o = _overlap(s, s + d, w0, w1)
            if not o:
                continue
            modules_s[n] = modules_s.get(n, 0.0) + o / 1e9
            label = _label(spans, s + d // 2)
            if label is not None:
                t, c = in_span.setdefault(label, {}).get(n, (0.0, 0))
                in_span[label][n] = (t + o / 1e9, c + 1)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
    idle = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        idle.append([_label(spans, (g0 + g1) // 2) or "no host span", (g1 - g0) / 1e9])
    n = len(devs)
    window_s = (w1 - w0) / 1e9
    return {
        "chips": n,
        "window_s": window_s,
        "busy_s": busy_total / n / 1e9,
        "modules_s": modules_s,
        "in_span": in_span,
        "device_ops": sorted(ops_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle,
    }
