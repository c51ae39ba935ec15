"""Engine: mean host time the engine spends between one decode step's
token vector reaching the host and the next decode program's dispatch,
prefills and wave hooks left out (``engine/host_step_s``): its own share
of each gap between decode steps."""
from chipbench.metrics import _registry


def read(data):
    v = _registry.mean(data, "engine/host_step_s")
    return None if v is None else 1e3 * v
