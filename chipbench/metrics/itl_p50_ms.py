"""Median gap between consecutive output tokens, over all requests due in
the window: the decode step as users feel it."""
from chipbench import latency


def read(data):
    v = latency.itl_s(data)
    return 1e3 * latency.percentile(v, 50) if v else None
