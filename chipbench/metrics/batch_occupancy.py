"""Scheduler: mean share of the decode wave's slots that hold a live
request (``engine/wave_batch_density``)."""
from chipbench.metrics import _registry


def read(data):
    v = _registry.mean(data, "engine/wave_batch_density")
    return None if v is None else 100.0 * v
