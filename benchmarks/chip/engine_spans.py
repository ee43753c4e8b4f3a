"""Reductions of the engines' own spans and chunk program from a profiler
trace (``tracing.load``).

The engines mark their layers with ``repro.spans``: ``sim.stage``,
``sim.dispatch``, ``sim.snapshot`` and ``sim.fetch`` are host events on
the thread that holds the harness's spans, so ``tracing.load`` reads them
with the harness's own; every engine's chunk program is the XLA module
``jit_sim_chunk``. A trace without them reduces to empty results.

- span totals: for each ``sim.*`` name, the union of its events clipped
  to the window (nested events of one name count once) and their number;
- chunk busy time of a device: the union of the operations that ran in a
  ``jit_sim_chunk`` module, found as ``tracing.top_ops`` finds modules;
- idle by span: the device's idle time, each part of it put down to the
  innermost ``sim.*`` span that covers it. A gap is split where the
  spans change, not named whole by its midpoint: a long gap that holds a
  read and the Python around it gives each its share, which a label at
  the midpoint puts all on one of them, by chance.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

import tracing

PREFIX = "sim."
CHUNK_MODULE = "jit_sim_chunk"
OUTSIDE = "outside the engine's spans"


def _engine_events(trace: tracing.Trace):
    return [ev for ev in trace.host if ev[2].startswith(PREFIX)]


def span_totals(trace: tracing.Trace, lo: float, hi: float
                ) -> Dict[str, Tuple[float, int]]:
    """``{name: (seconds, events)}`` for each ``sim.*`` span in [lo, hi]."""
    by_name: Dict[str, List[tracing.Interval]] = defaultdict(list)
    for s, e, name in _engine_events(trace):
        if e > lo and s < hi:
            by_name[name].append((s, e))
    return {name: (sum(e - s for s, e in tracing.union(iv, lo, hi)) / 1e9,
                   len(iv)) for name, iv in sorted(by_name.items())}


def module_busy_ns(dev: tracing.DeviceTimeline, lo: float, hi: float,
                   module: str = CHUNK_MODULE) -> float:
    """Busy time of ``dev`` in [lo, hi] in operations of ``module``."""
    starts = [m[0] for m in dev.modules]

    def ran_in_module(s):
        k = bisect.bisect_right(starts, s) - 1
        return k >= 0 and dev.modules[k][1] >= s and \
            tracing.MODULE_HASH.sub("", dev.modules[k][2]) == module

    ops = ((s, e) for s, e, _ in dev.ops if ran_in_module(s))
    return sum(e - s for s, e in tracing.union(ops, lo, hi))


def innermost(events: List[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Nested host events, sorted outer first where two start together,
    as disjoint sorted segments, each named by the innermost event that
    covers it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []          # (end, name), outer first
    t = 0.0

    def close_until(s):
        nonlocal t
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for s, e, name in events:
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = max(t, s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    close_until(float("inf"))
    return out


def idle_by_span(trace: tracing.Trace, lo: float, hi: float) -> List[List]:
    """Idle device time in [lo, hi] per innermost ``sim.*`` span, in
    seconds averaged over the devices, longest first."""
    segs = innermost(_engine_events(trace))
    total: Dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        busy = tracing.union(((s, e) for s, e, _ in dev.ops), lo, hi)
        j = 0
        for gs, ge in tracing.gaps(busy, lo, hi):
            while j < len(segs) and segs[j][1] <= gs:
                j += 1
            covered = 0.0
            k = j
            while k < len(segs) and segs[k][0] < ge:
                s, e, name = segs[k]
                part = min(e, ge) - max(s, gs)
                total[name] += part
                covered += part
                k += 1
            if ge - gs > covered:
                total[OUTSIDE] += ge - gs - covered
    n = max(len(trace.devices), 1)
    rows = sorted(total.items(), key=lambda kv: -kv[1])
    return [[name, ns / n / 1e9] for name, ns in rows]
