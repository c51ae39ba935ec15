"""The work functions of ``decode_roofline`` and ``step_mfu`` against
counts worked out by hand from the published sizes."""
import json
import os

import pytest

from chipbench.metrics import decode_roofline as dr
from chipbench.metrics import step_mfu as mfu

CONF = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def conf(name):
    with open(os.path.join(CONF, f"{name}.json")) as f:
        return json.load(f)


# parameters of one layer, from the published widths
# qwen1.5-0.5b: q, k, v, o 1024x1024 each, SwiGLU 3 x 1024x2816, two
# norms of 1024, q/k/v biases 3 x 1024
QWEN_LAYER = 4 * 1024 * 1024 + 3 * 1024 * 2816 + 2 * 1024 + 3 * 1024
# mistral-large: q and o 12288x12288, k and v 12288x1024, SwiGLU
# 3 x 12288x28672, two norms
MISTRAL_LAYER = (2 * 12288 * 12288 + 2 * 12288 * 1024 + 3 * 12288 * 28672
                 + 2 * 12288)


def test_layer_params_by_hand():
    from chipbench import weights as W
    assert QWEN_LAYER == 12_850_176
    assert MISTRAL_LAYER == 1_384_144_896
    assert dr.layer_params(W.dims(conf("qwen1.5-0.5b"))) == QWEN_LAYER
    assert dr.layer_params(
        W.dims(conf("mistral-large-123b-2l"))) == MISTRAL_LAYER


@pytest.mark.parametrize("case", ["qwen_docqa", "qwen_unshared",
                                  "mistral_closed"])
def test_decode_step_work(case):
    ctx = [100, 200]
    if case.startswith("qwen"):
        c = conf("qwen1.5-0.5b")
        corpus = 32768 if case == "qwen_docqa" else 0
        L, V, d, layer = 24, 151936, 1024, QWEN_LAYER
        kv = 2 * 16 * 64 * 2              # K and V, 16 heads of 64, bf16
        hq = 16 * 64
        E, k = (16, 4) if corpus else (0, 0)
    else:
        c = conf("mistral-large-123b-2l")
        corpus = 8192
        L, V, d, layer = 2, 32768, 12288, MISTRAL_LAYER
        kv = 2 * 8 * 128 * 2
        hq = 96 * 128
        E, k = 4, 1
    mix = {"corpus_tokens": corpus}
    weights = L * layer + V * d + d
    hit = E * (1 - (1 - k / E) ** 2) if E else 0.0
    want_bytes = 2 * (weights + 2 * d) + L * kv * (300 + hit * 2048)
    want_flops = 2 * 2 * weights + 4 * L * hq * (300 + 2 * k * 2048)
    f, b = dr.step_work(c, mix, ctx)
    assert f == pytest.approx(want_flops, rel=1e-12)
    assert b == pytest.approx(want_bytes, rel=1e-12)
    peaks = {"peak_flops_bf16": 197e12, "hbm_bw": 819e9}
    assert dr.bound(c, mix, peaks, ctx) == "memory"
    assert dr.least_time(c, mix, peaks, ctx) == pytest.approx(
        want_bytes / 819e9)
    # step_mfu counts the same decode FLOPs
    assert mfu.decode_flops(c, mix, ctx) == pytest.approx(want_flops)
    # a prompt of 300: weights for every token, causal attention over
    # 300*301/2 keys, the routed chunks for every token, one LM head
    want_pre = (2 * 300 * L * layer + 2 * d * V
                + 4 * L * hq * (300 * 301 / 2 + 300 * k * 2048))
    assert mfu.prefill_flops(c, mix, 300) == pytest.approx(want_pre)


def test_qwen_weights_are_464m():
    from chipbench import weights as W
    m = W.dims(conf("qwen1.5-0.5b"))
    n = m["L"] * dr.layer_params(m) + m["V"] * m["d"] + m["d"]
    assert n == 463_987_712
