"""Seconds the window's demand faults waited on tier-1 loads another loader
had in flight, the prefetcher's (the program's spans repro.tier1.wait of
source fault)."""

from bench import program_spans


def read(rec):
    return program_spans.seconds(rec, "repro.tier1.wait", "fault")
