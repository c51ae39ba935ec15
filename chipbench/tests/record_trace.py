#!/usr/bin/env python3
"""Record the small profiler traces that ``test_trace_reduce.py`` reads,
on the chip this process finds:

- ``data/small.xplane.pb``: two jitted programs, run a few times under the
  harness's own ``TraceAnnotation`` spans with host sleeps between them;
- ``data/scan.xplane.pb`` (``--scan``): a program whose ``lax.scan`` makes
  a matrix product and a host callback that sleeps 5 ms in each of its 4
  steps, as the program's layer scan calls its jit metrics, run 3 times.

    python3 chipbench/tests/record_trace.py <out.xplane.pb> [--scan]
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


SCAN_STEPS, SCAN_RUNS, CALLBACK_S = 4, 3, 0.005


def _scan_program():
    def step(h, _):
        h = jnp.tanh(h @ h)
        jax.debug.callback(lambda v: time.sleep(CALLBACK_S), h[0, 0])
        return h, None

    return jax.jit(lambda x: jax.lax.scan(step, x, None,
                                          length=SCAN_STEPS)[0].sum())


def main(out: str, scan: bool = False) -> int:
    x = jnp.ones((1024, 1024), jnp.float32)
    if scan:
        prog = _scan_program()
        prog(x).block_until_ready()
        d = tempfile.mkdtemp()
        jax.profiler.start_trace(d)
        for _ in range(SCAN_RUNS):
            with jax.profiler.TraceAnnotation("bench.engine_run"):
                prog(x).block_until_ready()
        jax.profiler.stop_trace()
        return _save(d, out)
    mm = jax.jit(lambda x: (x @ x).sum())
    ew = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    mm(x).block_until_ready()
    ew(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.engine_run"):
            mm(x).block_until_ready()
            ew(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle_wait"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    return _save(d, out)


def _save(d: str, out: str) -> int:
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(d)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], "--scan" in sys.argv[2:]))
