"""Bytes of the tier-1 placeholders the in-window cold start put on the
device: every tier-1 leaf at full shape (the program's
ColdStartReport.placeholder_bytes)."""


def read(rec):
    v = rec.cold.get("placeholder_bytes") if rec.cold else None
    return float(v) if v is not None else None
