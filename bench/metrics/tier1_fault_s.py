"""Seconds the serving steps of the window waited on tier-1 fault-in: the
engine's own per-step fault time, summed over every prefill and decode."""


def read(rec):
    return sum(s.fault_s for s in rec.steps) if rec.steps else None
