"""Whole step: model FLOPs of every token prefilled and decoded in the
traced window, over the window times the chip's peak bf16 FLOP/s.

Per token: two FLOPs per layer weight, plus attention over its unique
context (causal in the prompt) and over the ``top_k x chunk_size`` shared
tokens it is routed to, four FLOPs per key per query head; the LM head,
two FLOPs per weight, for each token whose logits are taken (one per
prompt, every decoded token). Bucket padding is not work and is not
counted.
"""
from __future__ import annotations

from chipbench import weights as W
from chipbench.metrics.decode_roofline import (layer_params, shared_chunks,
                                               step_work)


def prefill_flops(conf, mix, plen: int) -> float:
    m = W.dims(conf)
    L, d, V, H, D = m["L"], m["d"], m["V"], m["H"], m["D"]
    _, k, C = shared_chunks(conf, mix)
    causal = plen * (plen + 1) / 2.0
    return (2.0 * plen * L * layer_params(m) + 2.0 * d * V
            + 4.0 * L * H * D * (causal + plen * k * C))


def decode_flops(conf, mix, ctx) -> float:
    """The FLOPs ``decode_roofline`` counts for one decode step."""
    return step_work(conf, mix, ctx)[0]


def read(data):
    if data.seconds <= 0 or not data.waves:
        return None
    f = 0.0
    for w in data.waves:
        f += sum(prefill_flops(data.conf, data.mix, p) for p in w.prefill_lens)
        if w.decode_ctx:
            f += decode_flops(data.conf, data.mix, w.decode_ctx)
    return 100.0 * f / (data.seconds * data.peaks["peak_flops_bf16"])
