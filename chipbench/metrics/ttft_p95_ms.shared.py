"""95th percentile of time to first token, read per layer in the cells
whose end-to-end line leaves it out: with a shared corpus, a window holds
few requests and each waits whole waves of batch-1 prefills, so the tail
moves by a wave from run to run. Same reading as ``ttft_p95_ms``, over the
traced stretch."""
from chipbench import latency


def read(data):
    v = latency.ttft_s(data)
    return 1e3 * latency.percentile(v, 95) if v else None
