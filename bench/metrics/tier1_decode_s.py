"""Seconds the window's demand faults spent decoding tier-1 frames on the
host (the program's spans repro.tier1.decode of source fault)."""

from bench import program_spans


def read(rec):
    return program_spans.seconds(rec, "repro.tier1.decode", "fault")
