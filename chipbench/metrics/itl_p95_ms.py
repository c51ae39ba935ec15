"""95th percentile gap between consecutive output tokens: where a prefill
that blocks the decode wave shows."""
from chipbench import latency


def read(data):
    v = latency.itl_s(data)
    return 1e3 * latency.percentile(v, 95) if v else None
