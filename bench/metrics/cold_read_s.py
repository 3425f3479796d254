"""Seconds the in-window cold start spent reading the artifact's tier-0
from storage (the program's own ColdStartReport.read_s)."""


def read(rec):
    return rec.cold["read_s"] if rec.cold else None
