"""The program's own spans and scopes in a JAX profiler trace.

The study path names its layers in two ways (``docs/sweep.md``, "Tracing a
study"): host spans ``sweep.study``, ``sweep.stage``, ``sweep.dispatch``,
``sweep.fetch`` and ``sweep.summarize`` (``jax.profiler.TraceAnnotation``),
and the device scopes ``renewal_sample``, ``renewal_scan`` and
``renewal_fold`` (``jax.named_scope``), which the compiler keeps in each
instruction's metadata name (``jit(f)/vmap(vmap(renewal_fold))/mul``).
From the trace of a window that ``bench/xplane.py`` reduces, this module
reads:

* the device's busy time by scope: where operation events nest (a
  ``while`` and the operations of its body), each busy nanosecond goes to
  the innermost operation, the latest started, so it counts once, and the
  scopes plus the unscoped time add up to the busy time ``xplane`` reads;
* the device-idle time inside each study (``bench.<span>``), by what the
  study's host thread was in: a program span innermost (the program's own
  host work), a JAX event innermost (argument transfers, the copy of the
  results to the host), or nothing but the study's span (the driver's
  share).

A trace names an operation by its HLO instruction (a TPU's ``XLA Ops``
event by the instruction's text, ``%fusion.1 = pred[...] fusion(...)``, a
CPU event by the instruction's name); the compiled program's text
(``hlo_scopes``) gives the instruction's metadata name, so its scope.
"""
from __future__ import annotations

import bisect
import heapq
import re

from bench import xplane

SCOPES = ("renewal_sample", "renewal_scan", "renewal_fold")
PROGRAM_PREFIX = "sweep."
_WRAPPED = re.compile(r"[\w.-]+\((.*)\)")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=.*?\bop_name="([^"]*)"', re.M)


def scope_of(op_name: str):
    """The scope of SCOPES that a metadata name passes through, with
    transform wrappers (``vmap(...)``, ``jit(...)``) taken off each path
    component, or None."""
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.fullmatch(part)
            if m is None:
                break
            part = m.group(1)
        if part in SCOPES:
            return part
    return None


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope (or None) of every instruction with a
    metadata name in a compiled program's text (``Compiled.as_text()``)."""
    return {name: scope_of(op) for name, op in _INSTRUCTION.findall(hlo_text)}


def instruction(op: str) -> str:
    """The HLO instruction an operation event names: a TPU event's name
    is the instruction's text (``%fusion.1 = pred[...] fusion(...)``), a
    CPU event's the instruction's name."""
    m = re.match(r"%?([\w.-]+)", op)
    return m.group(1) if m else op


def scoped_ops(pd) -> list:
    """Per device: the operation events that scopes are read from.  On a
    TPU, those ``xplane.device_ops`` reads.  On the CPU, every event of
    the host threads that names an HLO instruction (an ``hlo_op`` stat):
    the XLA client's thread, which ``xplane`` reads, mostly waits there
    while worker threads run the instructions."""
    if any(p.name.startswith("/device:TPU:") for p in pd.planes):
        return xplane.device_ops(pd)
    ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events
           if ev.duration_ns > 0 and any(k == "hlo_op" for k, _ in ev.stats)]
    return [ops] if ops else []


def self_times(busy, ops, lo, hi, scope) -> dict:
    """Scope (None: unscoped) -> nanoseconds of the merged ``busy``
    intervals within ``[lo, hi]``, each given to the innermost (latest
    started) of the operations ``ops`` (``(name, start_ns, end_ns)``)
    running then; ``scope`` maps an operation's name to its scope."""
    busy = xplane.clip(busy, lo, hi)
    order = sorted((s, e, n) for n, s, e in ops if e > s)
    cuts = sorted({t for iv in busy for t in iv}
                  | {t for s, e, _ in order for t in (s, e) if lo < t < hi})
    out, heap, i, j = {}, [], 0, 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][0] <= a:
            s, e, n = order[i]
            heapq.heappush(heap, (-s, e, n))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        if j == len(busy) or busy[j][0] > a:
            continue
        k = scope(heap[0][2]) if heap else None
        out[k] = out.get(k, 0.0) + (b - a)
    return out


def idle_split(busy, line, span) -> dict:
    """Nanoseconds of device-idle time inside the ``span`` events of the
    host ``line``, by what the line was in: ``"program"`` -> {program span
    -> ns} where a ``sweep.*`` span was innermost, ``"jax"`` -> {program
    span -> ns} where another event was innermost (keyed by the program
    span around it, None outside every one), ``"driver"`` where only
    ``span`` was open.  ``busy`` is the device's merged busy intervals."""
    inner = [ev for ev in line if not ev[0].startswith(xplane.SPAN_PREFIX)]
    program = [ev for ev in inner if ev[0].startswith(PROGRAM_PREFIX)]
    cuts = sorted({t for _, s, e in inner for t in (s, e)})
    # the idle stretches, cut wherever a host event starts or ends
    pieces = []
    for _, lo, hi in (ev for ev in line if ev[0] == span):
        for s, e in xplane.gaps(busy, lo, hi):
            i, j = bisect.bisect_right(cuts, s), bisect.bisect_left(cuts, e)
            bounds = [s, *cuts[i:j], e]
            pieces += zip(bounds, bounds[1:])
    pieces.sort()
    mids = [(a + b) / 2 for a, b in pieces]
    out = {"program": {}, "jax": {}, "driver": 0.0}
    for (a, b), what, around in zip(pieces, xplane.innermost(inner, mids),
                                    xplane.innermost(program, mids)):
        if what is None:
            out["driver"] += b - a
        else:
            kind = "program" if what == around else "jax"
            out[kind][around] = out[kind].get(around, 0.0) + (b - a)
    return out


def summarize(device, ops, host, scope, span: str = "bench.study") -> dict:
    """The window's numbers, in seconds: the device's busy time by scope,
    and the idle time inside the ``span`` events split by ``idle_split``.
    ``device`` and ``host`` are ``xplane.device_ops``/``xplane.host_lines``,
    ``ops`` is ``scoped_ops`` (the first device's idle time is split);
    ``scope`` maps an operation name to its scope."""
    line = next((ln for ln in host if any(ev[0] == xplane.WINDOW
                                          for ev in ln)), None)
    if line is None or not device:
        return {}
    _, lo, hi = next(ev for ev in line if ev[0] == xplane.WINDOW)
    busy = [xplane.merge((s, e) for _, s, e in d) for d in device]
    per_device = [self_times(b, o, lo, hi, scope)
                  for b, o in zip(busy, ops or [[]] * len(busy))]
    self_s = {k: sum(d.get(k, 0.0) for d in per_device) / len(device) / 1e9
              for k in {k for d in per_device for k in d}}
    idle = idle_split(busy[0], line, span)
    return dict(
        studies=sum(1 for ev in line if ev[0] == span),
        busy_s=sum(self_s.values()),
        scopes_s={k: self_s.get(k, 0.0) for k in SCOPES},
        unscoped_s=self_s.get(None, 0.0),
        program_idle_s={k: v / 1e9 for k, v in idle["program"].items()},
        jax_idle_s={k: v / 1e9 for k, v in idle["jax"].items()},
        driver_idle_s=idle["driver"] / 1e9)


def reduce(trace_dir: str, hlo_text: str) -> dict:
    """``summarize`` of the one trace under ``trace_dir``; ``hlo_text`` is
    the compiled text of the programs the window ran."""
    pd = xplane.load(trace_dir)
    names = hlo_scopes(hlo_text)
    return summarize(xplane.device_ops(pd), scoped_ops(pd),
                     xplane.host_lines(pd),
                     lambda op: names.get(instruction(op)))
