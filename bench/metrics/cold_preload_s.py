"""Seconds the in-window cold start spent faulting the hot set in
(the program's ColdStartReport.preload_s, span repro.cold.preload)."""


def read(rec):
    return rec.cold.get("preload_s") if rec.cold else None
