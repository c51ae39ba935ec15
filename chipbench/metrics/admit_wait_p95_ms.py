"""Scheduler: 95th percentile, over the requests due in the window, of the
program's own stamps from a request's submission (``Request.arrival``) to
the scheduler giving it a slot (``Request.admitted_at``). A request with
no slot when the window closes enters with its wait so far. A program
whose requests carry no such stamps reads nothing."""
from chipbench import latency


def read(data):
    waits = []
    for r in data.recs:
        req = r.req
        if not hasattr(req, "admitted_at"):
            return None
        got = req.admitted_at
        if got is None or got > data.t1:
            got = data.t1
        waits.append(max(0.0, got - req.arrival))
    return 1e3 * latency.percentile(waits, 95) if waits else None
