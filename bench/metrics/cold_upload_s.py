"""Seconds the in-window cold start spent putting tier-0 and the tier-1
placeholders on the device and preloading the hot set (the program's own
ColdStartReport.upload_s)."""


def read(rec):
    return rec.cold["upload_s"] if rec.cold else None
