"""Seconds the in-window cold start spent building the tier-1 placeholders
on the host, putting them on the device and waiting for every put, tier-0's
too (the program's ColdStartReport.placeholder_s, span
repro.cold.placeholder)."""


def read(rec):
    return rec.cold.get("placeholder_s") if rec.cold else None
