"""Seconds the in-window cold start spent allocating the tier-1
placeholders on the device and waiting once for every put, tier-0's too
(the program's ColdStartReport.placeholder_s, span repro.cold.placeholder,
which holds repro.cold.put_wait)."""


def read(rec):
    return rec.cold.get("placeholder_s") if rec.cold else None
