"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
device program, time per device operation and idle gaps.

A device plane is one whose name starts with ``/device:TPU:``. On it the
line ``XLA Ops`` holds one event per operation that ran and the line
``XLA Modules`` one event per program execution (a jitted function), named
after the program.

Busy time is the union of the intervals of the leaf operations, per
device, averaged over the devices. Two kinds of event are not leaf work:

- a container (``while``, ``conditional``, ``call``) spans every operation
  of its body, and the waits between them; it is any event that holds
  another event of the line;
- a host wait (a host callback such as ``debug_callback``, or a transfer
  to or from the host) is the device stopped until the host answers.

Host waits are reported apart (``host_wait_s``) and count as idle. An
idle gap is a stretch between two busy intervals; it is named by the
innermost host event on the thread that drives the device (the harness's
own ``TraceAnnotation`` spans and JAX's dispatch events) that covers the
gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
HOST_WAIT_OPCODES = ("send", "recv", "send-done", "recv-done", "infeed",
                     "outfeed")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def opcode(full_name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event's name, ``"%x.3 = f32[2]{0}
    add(...)"``: the word before the operand list ("" where there is
    none)."""
    rhs = full_name.split(" = ", 1)[1] if " = " in full_name else ""
    m = re.search(r"([A-Za-z][\w-]*)\(", rhs)
    return m.group(1) if m else ""


def is_host_wait(full_name: str) -> bool:
    short = full_name.split(" = ")[0]
    return "callback" in short or opcode(full_name) in HOST_WAIT_OPCODES


def containers(iv: List[Tuple[float, float]]) -> List[bool]:
    """For each interval, whether it holds another interval of the list
    (starts no later and ends no earlier); equal intervals count the later
    one as held."""
    order = sorted(range(len(iv)), key=lambda i: (iv[i][0], -iv[i][1]))
    out = [False] * len(iv)
    stack: List[int] = []
    for i in order:
        s, e = iv[i]
        while stack and iv[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= iv[stack[-1]][1]:
            out[stack[-1]] = True
        stack.append(i)
    return out


def split_busy(events: List[Tuple[str, float, float]]):
    """(busy, host wait) of one device's ``XLA Ops`` events ``(name, start,
    duration)``: the union of the leaf work, and the host waits as
    ``(start, end, label)`` sorted by start; containers left out."""
    iv = [(s, s + d) for _, s, d in events]
    held = containers(iv)
    work, wait = [], []
    for (name, _, _), (s, e), c in zip(events, iv, held):
        if c:
            continue
        if is_host_wait(name):
            short = re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))
            wait.append((s, e, f"host wait: {short}"))
        else:
            work.append((s, e))
    return _union(work), sorted(wait)


def _total(u: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in u) * 1e-9


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, float(ev.start_ns), float(ev.duration_ns)


def reduce(path: str, host_thread: Optional[str] = None,
           top: int = 10) -> Dict:
    """Reduce the trace at ``path``.

    Returns ``devices`` (count), ``busy_s`` (union of leaf-op intervals,
    host waits left out, mean over devices), ``host_wait_s`` (union of
    host-wait intervals, mean over devices), ``all_ops_s`` (union of every
    op interval, containers included), ``span_s`` (first op start to last
    op end, mean over devices), ``modules`` {name: [count, seconds]} and ``ops`` {name:
    [count, seconds]} summed over devices, ``gaps`` the ``top`` longest
    idle gaps on the first device as [label, seconds], and ``gap_total``
    {label: seconds} over all of its gaps.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    dev.sort(key=lambda p: p.name)
    modules: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    busy, wait, every, spans, first_busy = [], [], [], [], None
    for p in dev:
        evs = list(_events(p, OPS_LINE))
        for name, s, d in evs:
            name = name.split(" = ")[0]
            ops[name][0] += 1
            ops[name][1] += d * 1e-9
        for name, s, d in _events(p, MODULES_LINE):
            modules[name][0] += 1
            modules[name][1] += d * 1e-9
        work, hw = split_busy(evs)
        u = _union([(s, s + d) for _, s, d in evs])
        busy.append(_total(work))
        wait.append(_total(_union([(s, e) for s, e, _ in hw])))
        every.append(_total(u))
        spans.append((u[-1][1] - u[0][0]) * 1e-9 if u else 0.0)
        if first_busy is None:
            first_busy, first_wait = work, hw
    gaps, gap_total = [], collections.defaultdict(float)
    if first_busy:
        host = _host_events(pd, host_thread)
        for (s0, e0), (s1, _) in zip(first_busy, first_busy[1:]):
            t = (e0 + s1) / 2
            label = _covering(first_wait, t) or _label(host, t)
            gaps.append([label, (s1 - e0) * 1e-9])
            gap_total[label] += (s1 - e0) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    n = max(len(dev), 1)
    return {"devices": len(dev), "busy_s": sum(busy) / n,
            "host_wait_s": sum(wait) / n, "all_ops_s": sum(every) / n,
            "span_s": sum(spans) / n, "modules": dict(modules),
            "ops": dict(ops), "gaps": gaps[:top],
            "gap_total": dict(gap_total)}


def _covering(events, t: float) -> Optional[str]:
    """The label of the ``(start, end, label)`` event, sorted by start,
    that covers ``t``; None where none does."""
    i = bisect.bisect_right(events, (t, float("inf"), ""))
    for s, e, label in reversed(events[max(0, i - 8):i]):
        if s <= t <= e:
            return label
    return None


def _host_events(pd, thread: Optional[str]):
    """(start, end, name) of the host events on the thread that drives the
    device: the given thread, else the one with the most of the harness's
    own spans (names starting ``SPAN_PREFIX``), else the busiest."""
    best, key = [], (-1, -1)
    for p in pd.planes:
        if p.name != HOST_PLANE:
            continue
        for line in p.lines:
            if thread is not None and line.name != thread:
                continue
            evs = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name) for e in line.events]
            k = (sum(n.startswith(SPAN_PREFIX) for _, _, n in evs), len(evs))
            if k > key:
                best, key = evs, k
    return sorted(best)


def _label(host, t: float, look_back: int = 256) -> str:
    """What the host was doing at time ``t``: the innermost of the
    harness's spans that covers it, and the innermost host event of any
    kind under it where that is another (``"bench.engine_run / <event>"``).
    Of properly nested events the innermost is the one that starts last.
    ``host`` is sorted by start."""
    i = bisect.bisect_right(host, (t, float("inf"), ""))
    inner = span = None
    for s, e, name in reversed(host[max(0, i - look_back):i]):
        if e >= t:
            inner = inner or name
            if name.startswith(SPAN_PREFIX):
                span = name
                break
    if inner is None:
        return "no host event"
    if span is None or span == inner:
        return inner
    return f"{span} / {inner}"


def top_items(d: Dict[str, List[float]], n: int = 10):
    """[name, seconds] of the ``n`` entries with the most time."""
    items = sorted(d.items(), key=lambda kv: -kv[1][1])[:n]
    return [[k, v[1]] for k, v in items]
