"""Median over the window's decode steps of the host's wait on the step's
outputs (the program's spans repro.engine.decode_wait inside each
repro.sched.step that ran a decode): the device's share of a step, as the
host sees it."""

from bench import program_spans


def read(rec):
    steps = program_spans.decode_steps(rec)
    return None if steps is None else program_spans.median_ms([w for _, w, _ in steps])
