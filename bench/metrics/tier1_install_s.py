"""Seconds the window's demand faults spent installing tier-1 units, host
time: the host-to-device copy's dispatch, making room and the leaf update
(the program's spans repro.tier1.install of source fault)."""

from bench import program_spans


def read(rec):
    return program_spans.seconds(rec, "repro.tier1.install", "fault")
