"""``trace_reduce`` on small traces recorded on one TPU v5e by
``record_trace.py``: two jitted programs run three times under the
harness's ``bench.engine_run`` span, with 10 ms host sleeps under
``bench.idle_wait`` between the rounds (``small``); and a program whose
``lax.scan`` calls a host callback that sleeps in each step (``scan``)."""
import os

import pytest

from chipbench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(TRACE)


def test_one_device_busy_within_its_span(red):
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["span_s"]
    # three rounds with two 10 ms sleeps between them
    assert red["span_s"] >= 0.02


def test_programs_and_ops(red):
    mods = red["modules"]
    assert sum(c for c, _ in mods.values()) == 6
    assert all(c == 3 for c, _ in mods.values())
    assert len(mods) == 2
    ops_s = sum(s for _, s in red["ops"].values())
    assert ops_s == pytest.approx(red["busy_s"], rel=0.05)
    top = trace_reduce.top_items(red["ops"], 3)
    assert len(top) <= 3 and top == sorted(top, key=lambda x: -x[1])


def test_idle_gaps_are_named_by_host_spans(red):
    gaps = red["gaps"]
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # the two longest gaps are the sleeps between the rounds
    assert all(g[0].startswith("bench.idle_wait") for g in gaps[:2])
    assert all(g[1] >= 0.009 for g in gaps[:2])
    idle = sum(s for k, s in red["gap_total"].items()
               if k.startswith("bench.idle_wait"))
    assert idle >= 0.018


def test_scan_is_a_container_and_callbacks_wait_on_the_host():
    """The recorded scan: its ``while`` spans the whole program, so the
    union of every op reads the device busy; the leaf work is a small
    part, and the host callbacks (3 runs x 4 steps x 5 ms) are waits."""
    from chipbench.tests import record_trace as rec
    red = trace_reduce.reduce(os.path.join(DATA, "scan.xplane.pb"))
    assert red["devices"] == 1
    assert [c for c, _ in red["modules"].values()] == [rec.SCAN_RUNS]
    assert any(k.startswith("%while") for k in red["ops"])
    assert red["all_ops_s"] >= 0.9 * red["span_s"]
    calls = rec.SCAN_RUNS * rec.SCAN_STEPS * rec.CALLBACK_S
    assert red["host_wait_s"] >= 0.9 * calls
    assert red["busy_s"] < 0.1 * red["host_wait_s"]
    assert red["busy_s"] + red["host_wait_s"] <= red["all_ops_s"] * 1.001
    assert red["gaps"][0][0] == "host wait: debug_callback"
    assert red["gap_total"]["host wait: debug_callback"] >= 0.9 * calls


def test_union():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6), (6, 7)]) == \
        [(0, 3), (5, 7)]


def _ev(short, opcode, s, d):
    return (f"%{short} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", s, d)


def test_opcode_and_host_waits():
    assert trace_reduce.opcode(_ev("while.14", "while", 0, 1)[0]) == "while"
    assert trace_reduce.opcode(_ev("c.2", "copy-done", 0, 1)[0]) == \
        "copy-done"
    assert trace_reduce.opcode("%x.1") == ""
    assert trace_reduce.is_host_wait(_ev("debug_callback.252",
                                         "custom-call", 0, 1)[0])
    assert trace_reduce.is_host_wait(_ev("r.3", "recv-done", 0, 1)[0])
    assert not trace_reduce.is_host_wait(_ev("fusion.278", "fusion", 0, 1)[0])


@pytest.mark.parametrize("shift", [0.0, 1e9])
def test_containers_and_host_waits_are_not_busy(shift):
    """A layer scan: a ``while`` over [0, 100] holds two fusions and a host
    callback. Busy is the fusions alone; the callback is a host wait; the
    old union of every op read the whole ``while``."""
    evs = [_ev("while.14", "while", 0, 100),
           _ev("fusion.1", "fusion", 0, 20),
           _ev("debug_callback.7", "custom-call", 20, 50),
           _ev("fusion.2", "fusion", 70, 20),
           _ev("fusion.3", "fusion", 150, 10)]
    evs = [(n, s + shift, d) for n, s, d in evs]
    assert trace_reduce.containers([(s, s + d) for _, s, d in evs]) == \
        [True, False, False, False, False]
    work, wait = trace_reduce.split_busy(evs)
    assert [(s - shift, e - shift) for s, e in work] == \
        [(0, 20), (70, 90), (150, 160)]
    assert [(s - shift, e - shift, n) for s, e, n in wait] == \
        [(20, 70, "host wait: debug_callback")]


def test_containers_edge_cases():
    # adjacent intervals do not hold each other; a copy of one holds it
    assert trace_reduce.containers([(0, 10), (10, 20)]) == [False, False]
    assert trace_reduce.containers([(0, 10), (0, 10)]) == [True, False]
    # nested three deep: the two outer ones are containers
    assert trace_reduce.containers([(0, 10), (1, 9), (2, 3), (4, 5)]) == \
        [True, True, False, False]
