"""Scheduler: 95th percentile of the wait from a request's due time to the
start of the wave in which it got its slot."""
from chipbench import latency


def read(data):
    v = latency.queue_wait_s(data)
    return 1e3 * latency.percentile(v, 95) if v else None
