"""Tier-1 bytes the serving steps of the window faulted in on the request
path (the engine's own per-step count)."""


def read(rec):
    return float(sum(s.fault_bytes for s in rec.steps)) if rec.steps else None
