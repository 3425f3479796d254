"""Model FLOP/s utilisation of the serving steps: the operations every
prefill and decode step of the window needs (bench.work: the experts
routed, the positions occupied) over the steps' summed wall time, less
the time they waited on tier-1 fault-in, times the chip's peak bf16
FLOP/s (per cent)."""

from bench import work
from bench.peaks import peak


def read(rec):
    if not rec.steps:
        return None
    flops = 0.0
    for s in rec.steps:
        fn = work.prefill if s.kind == "prefill" else work.decode
        flops += fn(rec.arch, s.lengths, s.experts)[0]
    wall = sum(s.end - s.start - s.fault_s for s in rec.steps)
    return 100.0 * flops / (wall * peak(rec.device_kind).bf16_flops) if wall > 0 else None
