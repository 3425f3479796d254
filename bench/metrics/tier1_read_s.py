"""Seconds the window's demand faults spent reading tier-1 frames from the
store (the program's spans repro.tier1.read of source fault)."""

from bench import program_spans


def read(rec):
    return program_spans.seconds(rec, "repro.tier1.read", "fault")
