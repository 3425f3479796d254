"""Seconds the in-window cold start spent putting tier-0 on the device
(the program's ColdStartReport.tier0_put_s, span repro.cold.tier0_put)."""


def read(rec):
    return rec.cold.get("tier0_put_s") if rec.cold else None
