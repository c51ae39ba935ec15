"""The dense work module against counts worked out by hand from the
published sizes, and the two readers that count through a configuration's
work module: ``decode_roofline`` and ``step_mfu``."""
import json
import os

import pytest

from chipbench import loadgen
from chipbench.configs import dense_work as dw
from chipbench.metrics import decode_roofline as dr
from chipbench.metrics import step_mfu as mfu
from chipbench.peaks import peaks as peaks_of
from chipbench.tests import helpers

CONF = os.path.join(helpers.BENCH, "configs")


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
    assert dw.layer_params(W.dims(conf("qwen1.5-0.5b"))) == QWEN_LAYER
    assert dw.layer_params(
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
    f, b = dw.step_work(c, mix, ctx)
    assert f == pytest.approx(want_flops, rel=1e-12)
    assert b == pytest.approx(want_bytes, rel=1e-12)
    peaks = {"peak_flops_bf16": 197e12, "hbm_bw": 819e9}
    assert dr.bound(dw, c, mix, peaks, ctx) == "memory"
    assert dr.least_time(dw, c, mix, peaks, ctx) == pytest.approx(
        want_bytes / 819e9)
    # a prompt of 300: weights for every token, causal attention over
    # 300*301/2 keys, the routed chunks for every token, one LM head
    want_pre = (2 * 300 * L * layer + 2 * d * V
                + 4 * L * hq * (300 * 301 / 2 + 300 * k * 2048))
    assert dw.prefill_flops(c, mix, 300) == pytest.approx(want_pre)


def test_qwen_weights_are_464m():
    from chipbench import weights as W
    m = W.dims(conf("qwen1.5-0.5b"))
    n = m["L"] * dw.layer_params(m) + m["V"] * m["d"] + m["d"]
    assert n == 463_987_712


# -- the readers on a fixed traced window --------------------------------

def window_data(c, mix, work):
    """``RunData`` of the fixed window in ``data/work-window.json``."""
    from chipbench import run
    with open(os.path.join(helpers.DATA, "work-window.json")) as f:
        d = json.load(f)
    waves = [run.Wave(0.0, 0.0, w["prefill_lens"], w["decode_ctx"])
             for w in d["waves"]]
    trace = {"modules": {k: tuple(v) for k, v in d["modules"].items()}}
    return run.RunData(conf=c, mix=mix, work=work,
                       peaks=peaks_of("TPU v5 lite"), seconds=d["seconds"],
                       t0=0.0, t1=d["seconds"], recs=[], all_recs=[],
                       waves=waves, counters={}, setup_s=0.0, trace=trace)


# What the readers returned on this window at commit 10b1601, where the
# dense count lived in the readers themselves: a move of the count may not
# change a bit of either reading in a cell the benchmark has.
PARENT_READINGS = {
    "docqa": (36.8441866659576, 0.08242740390749347),
    "unshared": (14.34922926525763, 0.03694403853630456),
}


@pytest.mark.parametrize("traffic", sorted(PARENT_READINGS))
def test_readings_match_the_parent_bit_for_bit(traffic):
    mix = loadgen.load(traffic, helpers.BENCH)
    data = window_data(conf("qwen1.5-0.5b"), mix, dw)
    assert (dr.read(data), mfu.read(data)) == PARENT_READINGS[traffic]


STAND_IN = '''
def step_work(conf, mix, ctx):
    return 3.0e12 * len(ctx), 5.0e9 * len(ctx)


def prefill_flops(conf, mix, plen):
    return 7.0e9 * plen
'''


def _load_cell(tmp_path, work):
    """The test cell of a checkout whose configuration names ``work``
    (None: names no work module), with a stand-in work module beside
    it."""
    from chipbench import run
    bench = helpers.make_checkout(str(tmp_path))
    cdir = os.path.join(str(tmp_path), "chipbench", "configs")
    with open(os.path.join(cdir, "stand_in_work.py"), "w") as f:
        f.write(STAND_IN)
    path = os.path.join(cdir, "tiny.json")
    with open(path) as f:
        c = json.load(f)
    if work is None:
        del c["work"]
    else:
        c["work"] = work
    with open(path, "w") as f:
        json.dump(c, f)
    return run.load_cell(helpers.CELL, bench)


def test_readers_count_the_work_module_the_configuration_names(tmp_path):
    """A configuration that names a work module of its own, added as a new
    file, gets that module's FLOPs and bytes in both readers."""
    cell = _load_cell(tmp_path, "stand_in_work")
    assert cell.work.__file__.startswith(str(tmp_path))
    data = window_data(cell.conf, cell.mix, cell.work)
    p = peaks_of("TPU v5 lite")
    steps = [w.decode_ctx for w in data.waves if w.decode_ctx]
    least = sum(max(3.0e12 * len(c) / p["peak_flops_bf16"],
                    5.0e9 * len(c) / p["hbm_bw"]) for c in steps)
    n_dev, dev_s = data.trace["modules"]["jit__decode_impl"]
    assert dr.read(data) == pytest.approx(
        100.0 * least / len(steps) / (dev_s / n_dev), rel=1e-12)
    flops = sum(7.0e9 * sum(w.prefill_lens) + 3.0e12 * len(w.decode_ctx)
                for w in data.waves)
    assert mfu.read(data) == pytest.approx(
        100.0 * flops / (data.seconds * p["peak_flops_bf16"]), rel=1e-12)


def test_configuration_without_work_module_raises(tmp_path):
    with pytest.raises(ValueError, match="names no module for work") as e:
        _load_cell(tmp_path, None)
    assert os.path.join(str(tmp_path), "chipbench", "configs",
                        "tiny.json") in str(e.value)
