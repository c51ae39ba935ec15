"""95th percentile of time to first token over every request due in the
window, from when it was due; a request with no token at the window's end
enters with its wait so far."""
from chipbench import latency


def read(data):
    v = latency.ttft_s(data)
    return 1e3 * latency.percentile(v, 95) if v else None
