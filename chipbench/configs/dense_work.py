"""The work of a dense decoder with a shared corpus: every one of ``L``
layers is q/k/v/o attention plus a SwiGLU MLP, and keeps ``2 KH D`` values
of keys and values a token.

A configuration's file names its work module (``"work"``), and
``decode_roofline`` and ``step_mfu`` count through it. Every work module
has the same two functions:

- ``step_work(conf, mix, ctx) -> (flops, bytes)``: one decode step of the
  live requests whose unique contexts are ``ctx``;
- ``prefill_flops(conf, mix, plen) -> flops``: one prefill of ``plen``
  prompt tokens.

Work is counted from the configuration and the traffic alone, never from
the implementation. A module for another architecture counts what its
layers read and write each step (recurrent state, the weights of the
experts its tokens reach, a window's keys) and says which of those terms
it takes as expected values. Here one term is: the shared chunks a step
reads, the expected number of distinct chunks that B requests choosing
top-k of E uniformly would hit, ``E (1 - (1 - k/E)^B)``.

FLOPs are two per weight per token, plus four per cached key per query
head for attention. Bytes are every weight read once (the LM head
included), the unique keys and values of each live request at its actual
length, and the shared keys and values of the chunks hit.
"""
from __future__ import annotations

from chipbench import weights as W


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


def prefill_flops(conf, mix, plen: int) -> float:
    """Weights for every prompt token, attention over its unique context
    (causal in the prompt) and over the ``top_k x chunk_size`` shared
    tokens it is routed to, and the LM head once, for the first token.
    Bucket padding is not work and is not counted."""
    m = W.dims(conf)
    L, d, V, H, D = m["L"], m["d"], m["V"], m["H"], m["D"]
    _, k, C = shared_chunks(conf, mix)
    causal = plen * (plen + 1) / 2.0
    return (2.0 * plen * L * layer_params(m) + 2.0 * d * V
            + 4.0 * L * H * D * (causal + plen * k * C))
