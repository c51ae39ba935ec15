"""Engine: mean host time of one decode step, dispatch to the token
vector on the host (``engine/decode_step_latency_s``)."""
from chipbench.metrics import _registry


def read(data):
    v = _registry.mean(data, "engine/decode_step_latency_s")
    return None if v is None else 1e3 * v
