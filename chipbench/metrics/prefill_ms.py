"""Engine: mean host time of one admission's prefill, to its first token
on the host (``engine/prefill_latency_s``)."""
from chipbench.metrics import _registry


def read(data):
    v = _registry.mean(data, "engine/prefill_latency_s")
    return None if v is None else 1e3 * v
