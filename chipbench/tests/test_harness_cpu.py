"""The harness end to end on the CPU, at a test-only toy configuration,
in a throwaway checkout where that configuration, its mix, its cell and a
new metric were added as files and entries only."""
from __future__ import annotations

import json
import sys

import numpy as np
import pytest

from chipbench.tests import helpers

ARGS = ["--workload", helpers.CELL, "--seed", "3000000019", "--seconds",
        "1.5"]


def _run(tmp_path, capsys, trace=0, fault=None, extra_metric=""):
    bench = helpers.make_checkout(str(tmp_path), extra_metric=extra_metric)
    sys.path.insert(0, str(tmp_path))
    try:
        from chipbench import run
        rc = run.main(ARGS + ["--trace", str(trace)], require_tpu=False,
                      use_cache=False, bench_path=bench, fault=fault)
    finally:
        sys.path.remove(str(tmp_path))
    cap = capsys.readouterr()
    return rc, cap


def test_end_to_end_line_and_new_files_are_found(tmp_path, capsys):
    rc, cap = _run(tmp_path, capsys, extra_metric="requests_due")
    assert rc == 0
    out = helpers.last_json(cap.out)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "check"
    assert out["correct"] is True, cap.err[-2000:]
    assert out["attempted"] == 30 and out["failed"] == 0
    m = out["metrics"]
    for name in ("tokens_per_s", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms",
                 "setup_s"):
        assert m[name]["value"] > 0, name
    # the metric that exists only as a new file and a new entry
    assert m["requests_due"] == {"value": 30.0, "unit": "requests"}
    assert out["device"]["platform"] == "cpu"
    chk = out["check"]
    assert set(chk) == {"max_logit_gap", "tokens_compared"}
    assert chk["max_logit_gap"]["value"] <= chk["max_logit_gap"]["limit"]
    assert chk["tokens_compared"]["value"] >= chk["tokens_compared"]["limit"]
    assert "check max_logit_gap" in cap.err.strip().splitlines()[-2]


def test_traced_run_reports_per_layer_metrics(tmp_path, capsys):
    rc, cap = _run(tmp_path, capsys, trace=1)
    assert rc == 0
    out = helpers.last_json(cap.out)
    m = out["metrics"]
    for name in ("queue_wait_p95_ms", "ttft_p95_ms.shared",
                 "batch_occupancy", "decode_step_ms", "prefill_ms",
                 "dispatch_fill", "step_mfu"):
        assert name in m, name
    assert "tokens_per_s" not in m
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_token(server):
    """A token altered where it is produced: every decode step's output
    is shifted by one."""
    eng = server.engine
    dec = eng._decode

    def shifted(params, tokens, cache, store, use_store):
        nxt, cache = dec(params, tokens, cache, store, use_store=use_store)
        return (nxt + 1) % server.vocab_size, cache
    eng._decode = shifted


def _state_unchanged(server):
    """A step that returns its state unchanged: the decode step's cache
    comes back as it went in, so no key or value is ever appended."""
    import jax
    eng = server.engine
    plain = jax.jit(eng._decode_impl, static_argnames=("use_store",))

    def frozen(params, tokens, cache, store, use_store):
        nxt, _ = plain(params, tokens, cache, store, use_store=use_store)
        return nxt, cache
    eng._decode = frozen


def _shared_skipped(server):
    """The answer altered by leaving out the shared corpus: decode steps
    run without the store."""
    eng = server.engine
    dec = eng._decode

    def no_store(params, tokens, cache, store, use_store):
        return dec(params, tokens, cache, store, use_store=False)
    eng._decode = no_store


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _shared_skipped],
                         ids=["token_altered", "state_unchanged",
                              "shared_store_skipped"])
def test_broken_timed_path_is_not_correct(tmp_path, capsys, fault):
    rc, cap = _run(tmp_path, capsys, fault=fault)
    assert rc == 0
    out = helpers.last_json(cap.out)
    assert out["correct"] is False
    chk = out["check"]["max_logit_gap"]
    assert chk["value"] > chk["limit"], out["check"]


def test_control_fails_and_program_passes(tmp_path):
    """The control (the reference one precision step below the
    configuration's bfloat16: float8) fails the cell's limit on the same
    prompts and served tokens that the program, in bfloat16, passes. Every
    query attends to all of the corpus's chunks here (top-k = chunks), so
    that no routing near-tie, where bfloat16 and float32 may choose other
    chunks, stands in for the precision."""
    bench = helpers.make_checkout(str(tmp_path), dtype="bfloat16", top_k=4)
    sys.path.insert(0, str(tmp_path))
    try:
        from chipbench import control, run
        cell = run.load_cell(helpers.CELL, bench)
        system = run.prepare(cell, use_cache=False)
        r = control.readings(cell, system, 77, 1.5, control=True)
    finally:
        sys.path.remove(str(tmp_path))
    assert r["program_correct"] is True, r
    assert r["control_correct"] is False, r
    limit = cell.limits["compare"]["max_logit_gap"]
    assert r["program"]["max_logit_gap"] <= limit, r
    assert r["control"]["max_logit_gap"] > limit, r
    assert np.isfinite(r["control"]["max_logit_gap"])
