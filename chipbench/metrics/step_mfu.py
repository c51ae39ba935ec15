"""Whole step: model FLOPs of every token prefilled and decoded in the
traced window, over the window times the chip's peak bf16 FLOP/s.

The FLOPs are counted by the work module that the configuration's file
names (``chipbench/configs/<work>.py``): its ``prefill_flops`` for each
prompt prefilled and its ``step_work`` for each decode step, from the
configuration and the traffic alone.
"""
from __future__ import annotations


def read(data):
    if data.seconds <= 0 or not data.waves:
        return None
    work, f = data.work, 0.0
    for w in data.waves:
        f += sum(work.prefill_flops(data.conf, data.mix, p)
                 for p in w.prefill_lens)
        if w.decode_ctx:
            f += work.step_work(data.conf, data.mix, w.decode_ctx)[0]
    return 100.0 * f / (data.seconds * data.peaks["peak_flops_bf16"])
