"""MoSKA attention: mean share of the (chunk, capacity) query slots that
the routed dispatch fills (``moska/dispatch_capacity_utilization``), over
every layer of every step that attends a shared store."""
from chipbench.metrics import _registry


def read(data):
    v = _registry.mean(data, "moska/dispatch_capacity_utilization")
    return None if v is None else 100.0 * v
