"""Request latencies from token stamps: every time runs from the due time,
and a request still waiting when the window closes enters with its wait so
far."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import latency


def _data():
    recs = [NS(due=0.0, admitted=0.5, stamps=[1.0, 1.2, 1.5]),
            NS(due=2.0, admitted=2.25, stamps=[3.0, 3.5, 9.0]),
            NS(due=4.0, admitted=None, stamps=[])]
    return NS(recs=recs, t0=0.0, t1=5.0)


def test_ttft_counts_from_due_and_keeps_the_unserved():
    assert latency.ttft_s(_data()) == pytest.approx([1.0, 1.0, 1.0])


def test_itl_stops_at_the_window():
    assert latency.itl_s(_data()) == pytest.approx([0.2, 0.3, 0.5])


def test_queue_wait_counts_from_due_and_keeps_the_unadmitted():
    assert latency.queue_wait_s(_data()) == pytest.approx([0.5, 0.25, 1.0])
