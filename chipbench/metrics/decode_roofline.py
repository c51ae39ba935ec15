"""Model step: share of the decode program's device time that the work a
decode step needs would take at the chip's peaks.

The work is counted from the configuration and the traffic alone, never
from the implementation: every weight read once (the LM head included),
the unique keys and values of each live request at its actual length, and
the shared keys and values of the chunks the step routes to, taken as the
expected number of distinct chunks that B requests choosing top-k of E
uniformly would hit, ``E (1 - (1 - k/E)^B)``. FLOPs are two per weight per
request, plus four per cached key per query head for attention. The least
time is the larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth;
``bound()`` says which one binds.

The device time is that of the decode program's executions in the trace
(``XLA Modules`` events whose name contains ``DECODE_PROGRAM``).
"""
from __future__ import annotations

from chipbench import weights as W

DECODE_PROGRAM = "_decode_impl"


def layer_params(m) -> int:
    d, H, KH, D, F = m["d"], m["H"], m["KH"], m["D"], m["F"]
    n = d * H * D + 2 * d * KH * D + H * D * d + 3 * d * F + 2 * d
    if m["bias"]:
        n += H * D + 2 * KH * D
    return n


def kv_bytes_per_token_layer(m, itemsize: int = 2) -> int:
    return 2 * m["KH"] * m["D"] * itemsize


def shared_chunks(conf, mix):
    C = conf["moska"]["chunk_size"]
    E = mix.get("corpus_tokens", 0) // C
    return E, min(conf["moska"]["top_k_chunks"], E), C


def step_work(conf, mix, ctx, itemsize: int = 2):
    """(FLOPs, bytes) one decode step needs for live requests whose unique
    contexts (keys attended, the new token's included) are ``ctx``."""
    m = W.dims(conf)
    L, d, V, H, D = m["L"], m["d"], m["V"], m["H"], m["D"]
    B = len(ctx)
    E, k, C = shared_chunks(conf, mix)
    weights = L * layer_params(m) + V * d + d
    kvb = kv_bytes_per_token_layer(m, itemsize)
    hit = E * (1.0 - (1.0 - k / E) ** B) if E else 0.0
    nbytes = itemsize * (weights + B * d) + L * kvb * (sum(ctx) + hit * C)
    flops = 2.0 * B * weights + 4.0 * L * H * D * (sum(ctx) + B * k * C)
    return flops, nbytes


def least_time(conf, mix, peaks, ctx):
    f, b = step_work(conf, mix, ctx)
    return max(f / peaks["peak_flops_bf16"], b / peaks["hbm_bw"])


def bound(conf, mix, peaks, ctx) -> str:
    f, b = step_work(conf, mix, ctx)
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
    least = sum(least_time(data.conf, data.mix, data.peaks, c)
                for c in steps) / len(steps)
    return 100.0 * least / (dev_s / n_dev)
