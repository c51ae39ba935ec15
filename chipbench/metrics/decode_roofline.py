"""Model step: share of the decode program's device time that the work a
decode step needs would take at the chip's peaks.

The work, FLOPs and bytes of each decode step of the window, is counted
by the ``step_work`` of the work module that the configuration's file
names (``chipbench/configs/<work>.py``), from the configuration and the
traffic alone. The least time is the larger of FLOPs over peak FLOP/s and
bytes over HBM bandwidth; ``bound()`` says which one binds.

The device time is that of the decode program's executions in the trace
(``XLA Modules`` events whose name contains ``DECODE_PROGRAM``).
"""
from __future__ import annotations

DECODE_PROGRAM = "_decode_impl"


def least_time(work, conf, mix, peaks, ctx):
    f, b = work.step_work(conf, mix, ctx)
    return max(f / peaks["peak_flops_bf16"], b / peaks["hbm_bw"])


def bound(work, conf, mix, peaks, ctx) -> str:
    f, b = work.step_work(conf, mix, ctx)
    return ("compute" if f / peaks["peak_flops_bf16"] >=
            b / peaks["hbm_bw"] else "memory")


def decode_device_s(trace):
    n, s = 0, 0.0
    for name, (cnt, sec) in trace["modules"].items():
        if DECODE_PROGRAM in name:
            n, s = n + cnt, s + sec
    return n, s


def read(data):
    if not data.trace:
        return None
    n_dev, dev_s = decode_device_s(data.trace)
    steps = [w.decode_ctx for w in data.waves if w.decode_ctx]
    if not n_dev or dev_s <= 0 or not steps:
        return None
    least = sum(least_time(data.work, data.conf, data.mix, data.peaks, c)
                for c in steps) / len(steps)
    return 100.0 * least / (dev_s / n_dev)
