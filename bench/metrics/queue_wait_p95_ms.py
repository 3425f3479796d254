"""95th percentile over the window's requests of the wait from due time to
admission (the request rows' ``admitted - due``; the scheduler stamps
``admitted`` as the request's admission group starts its prefill), in ms.
A request still queued when the window closes counts with its wait so
far, as in ``ttft_p95_ms``. None where no request was admitted."""

import numpy as np


def read(rec):
    if not any(r["admitted"] is not None for r in rec.requests):
        return None
    waits = [(r["admitted"] if r["admitted"] is not None and r["admitted"] <= rec.seconds
              else rec.seconds) - r["due"] for r in rec.requests]
    return float(np.percentile(np.asarray(waits, float), 95)) * 1e3
