"""The readers of ``host_step_ms``, ``admit_wait_p95_ms`` and
``window_compile_s`` on synthetic run data: what each reads, and that a
program without the span, stamp or counter reads nothing."""
from types import SimpleNamespace as NS

import pytest

from chipbench import run
from chipbench.metrics import admit_wait_p95_ms, host_step_ms, \
    window_compile_s


def _data(recs=(), counters=None, t1=10.0):
    return NS(recs=list(recs), counters=counters or {}, t0=0.0, t1=t1)


def test_host_step_ms_is_the_histogram_mean():
    h = {"kind": "histogram", "count": 4, "sum": 0.010}
    assert host_step_ms.read(_data(counters={"engine/host_step_s": h})) \
        == pytest.approx(2.5)
    assert host_step_ms.read(_data()) is None
    empty = {"kind": "histogram", "count": 0, "sum": 0.0}
    assert host_step_ms.read(
        _data(counters={"engine/host_step_s": empty})) is None


def _rec(arrival, admitted_at):
    return NS(req=NS(arrival=arrival, admitted_at=admitted_at))


def test_admit_wait_keeps_the_unadmitted_and_stops_at_the_window():
    recs = [_rec(1.0, 1.5)] * 18 + [_rec(2.0, None), _rec(3.0, 12.0)]
    # waits: 18 x 0.5 s, then 8 s and 7 s (both cut at t1 = 10)
    got = admit_wait_p95_ms.read(_data(recs))
    assert got == pytest.approx(1e3 * run.latency.percentile(
        [0.5] * 18 + [8.0, 7.0], 95))
    assert got > 7000.0


def test_admit_wait_reads_nothing_without_stamps():
    assert admit_wait_p95_ms.read(_data([NS(req=NS(arrival=0.0))])) is None
    assert admit_wait_p95_ms.read(_data()) is None


def test_window_compile_s_reads_zero_as_a_reading():
    before = {"jax/backend_compile_s": {"kind": "counter", "value": 41.5}}
    same = run.counter_delta(before, before)
    assert window_compile_s.read(_data(counters=same)) == 0.0
    after = {"jax/backend_compile_s": {"kind": "counter", "value": 43.0}}
    assert window_compile_s.read(
        _data(counters=run.counter_delta(before, after))) == 1.5
    assert window_compile_s.read(_data()) is None
