"""Request latencies from the harness's token stamps, shared by the
end-to-end metrics and the knee sweep.

Every time is on the host clock and starts from when the request was due,
not from when it was sent, so a stall that delays later requests counts
against them. A request that has no first token when the window closes
enters the time-to-first-token tail with its wait so far.
"""
from __future__ import annotations

from typing import List

import numpy as np


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_s(data) -> List[float]:
    out = []
    for r in data.recs:
        first = r.stamps[0] if r.stamps and r.stamps[0] <= data.t1 else None
        out.append((first if first is not None else data.t1) - r.due)
    return out


def itl_s(data) -> List[float]:
    """Gaps between consecutive tokens of each request due in the window,
    as handed back by the engine's loop, up to the window's end."""
    out = []
    for r in data.recs:
        st = [s for s in r.stamps if s <= data.t1]
        out.extend(b - a for a, b in zip(st, st[1:]))
    return out


def queue_wait_s(data) -> List[float]:
    """Due time to the start of the wave that gave each request its slot;
    a request with no slot when the window closes enters with its wait so
    far."""
    out = []
    for r in data.recs:
        got = r.admitted if r.admitted is not None and \
            r.admitted <= data.t1 else data.t1
        out.append(max(0.0, got - r.due))
    return out
