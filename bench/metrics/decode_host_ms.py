"""Median over the window's decode steps of the scheduler step's wall time
(repro.sched.step) less the host's wait on the decode's outputs and less
its tier-1 fault time: the host's own work in a step (admission,
bookkeeping, hints, dispatch)."""

from bench import program_spans


def read(rec):
    steps = program_spans.decode_steps(rec)
    if steps is None:
        return None
    return program_spans.median_ms([wall - wait - fault for wall, wait, fault in steps])
