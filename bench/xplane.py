"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A traced run wraps its measured window in the host span ``bench.window``
and each call in ``bench.<span>`` (``jax.profiler.TraceAnnotation``), so
spans and device operations share the trace's clock.  From the trace:

* busy time: the union of the intervals in which an operation ran on the
  device, inside the window, averaged over the devices that ran any;
* the device operations that took most time (summed over their events);
* idle gaps: the stretches of the window with no device operation, each
  named by the harness span around its midpoint and by the innermost host
  event there (what the host was doing), summed by name;
* per call: the call's span and the device-busy time inside it.

On a TPU the device operations are the events of the ``XLA Ops`` line of
each ``/device:TPU:n`` plane.  A CPU backend has no device plane; there the
operations are the events of the XLA CPU client's threads (used by the
tests, which record a trace on the CPU).
"""
from __future__ import annotations

import bisect
import glob
import heapq
import itertools
import os

SPAN_PREFIX = "bench."
WINDOW = SPAN_PREFIX + "window"


def load(trace_dir: str):
    """The ``ProfileData`` of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    return ProfileData.from_file(paths[0])


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def device_ops(pd) -> list:
    """Per device: its operations as ``(name, start_ns, end_ns)``."""
    per_device = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [ev for line in plane.lines if line.name == "XLA Ops"
                   for ev in _events(line)]
            if ops:
                per_device.append(ops)
    if per_device:
        return per_device
    ops = [ev for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines if line.name.startswith("tf_XLAPjRtCpuClient")
           for ev in _events(line)
           if ev[2] > ev[1] and not ev[0].startswith(("ThreadpoolListener",
                                                      "end:"))]
    return [ops] if ops else []


def host_lines(pd) -> list:
    """The host threads' events, one list per line."""
    return [_events(line) for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines]


def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def coverage(busy):
    """``f(lo, hi)``: the length of ``[lo, hi]`` that the merged intervals
    ``busy`` cover (binary search over their prefix sums)."""
    starts = [s for s, _ in busy]
    ends = [e for _, e in busy]
    cum = [0.0] + list(itertools.accumulate(e - s for s, e in busy))

    def f(lo, hi):
        i = bisect.bisect_right(ends, lo)
        j = bisect.bisect_left(starts, hi)
        if i >= j:
            return 0.0
        return (cum[j] - cum[i] - max(0.0, lo - starts[i])
                - max(0.0, ends[j - 1] - hi))
    return f


def gaps(busy, lo, hi) -> list:
    """The stretches of ``[lo, hi]`` that merged ``busy`` leaves free."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(events, instants) -> list:
    """For each of the sorted ``instants``, the name of the innermost of
    the nested ``events`` covering it (the latest started), or None."""
    order = sorted(events, key=lambda ev: ev[1])
    heap, i, out = [], 0, []
    for t in instants:
        while i < len(order) and order[i][1] <= t:
            heapq.heappush(heap, (-order[i][1], order[i][2], order[i][0]))
            i += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else None)
    return out


def summarize(device, host, top: int = 10) -> dict:
    """The benchmark's numbers from per-device operations ``device`` and
    host lines ``host`` (``device_ops``/``host_lines``; times in ns)."""
    spans = [ev for line in host for ev in line
             if ev[0].startswith(SPAN_PREFIX)]
    windows = [ev for ev in spans if ev[0] == WINDOW]
    if not windows or not device:
        return {}
    _, lo, hi = windows[0]
    busy_each = [merge((s, e) for _, s, e in ops) for ops in device]
    busy_s = sum(coverage(b)(lo, hi) for b in busy_each) / len(busy_each) / 1e9
    busy = busy_each[0]
    cover = coverage(busy)
    totals = {}
    for name, s, e in device[0]:
        if e > lo and s < hi:
            totals[name] = totals.get(name, 0.0) + (min(e, hi) - max(s, lo))
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    # what the host did: the line that holds the harness spans
    line = next((ln for ln in host if any(ev[0] == WINDOW for ev in ln)), [])
    calls = [ev for ev in line
             if ev[0].startswith(SPAN_PREFIX) and ev[0] != WINDOW]
    inner = [ev for ev in line if not ev[0].startswith(SPAN_PREFIX)]
    free = gaps(busy, lo, hi)
    mids = [(s + e) / 2 for s, e in free]
    idle = {}
    for (s, e), span, what in zip(free, innermost(calls, mids),
                                  innermost(inner, mids)):
        span = span[len(SPAN_PREFIX):] if span else "between calls"
        label = f"{span}: {what}" if what else span
        idle[label] = idle.get(label, 0.0) + (e - s) / 1e9
    named = sorted(idle.items(), key=lambda kv: -kv[1])
    return dict(
        window_s=(hi - lo) / 1e9, busy_s=busy_s,
        device_ops=[[n, t / 1e9] for n, t in ops],
        idle_gaps=[[n, t] for n, t in named[:top]],
        calls=[((e - s) / 1e9, cover(s, e) / 1e9)
               for _, s, e in calls])


def reduce(trace_dir: str, top: int = 10) -> dict:
    pd = load(trace_dir)
    return summarize(device_ops(pd), host_lines(pd), top)
