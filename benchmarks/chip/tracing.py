"""Reductions from a JAX profiler trace to the numbers the metrics read.

The profiler writes an ``.xplane.pb`` file; ``jax.profiler.ProfileData``
reads it into planes, lines and events (start and duration in ns, on one
clock for host and device). Each TPU is a plane named ``/device:TPU:<n>``
whose ``XLA Ops`` line holds one event per operation run on it. The
harness's own host spans (``rebind``, ``run_batch``) are events on the
host plane's Python thread.

- busy time of a device: the union of its operation intervals, clipped to
  the traced window (operations can overlap; the union counts once);
- idle gaps: the parts of the window not covered by that union, each
  named by what the host was doing at its midpoint (the innermost host
  event on the thread that holds the harness's spans);
- top operations: device time summed per operation, each counted for its
  own time only (an operation's event can hold those of operations it
  runs), named by the program it ran in and its HLO instruction.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MODULE_HASH = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class DeviceTimeline:
    name: str
    ops: List[Tuple[float, float, str]]          # (start_ns, end_ns, name)
    modules: List[Tuple[float, float, str]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceTimeline]
    host: List[Tuple[float, float, str]] = field(default_factory=list)

    def spans(self, name: str) -> List[Interval]:
        return [(s, e) for s, e, n in self.host if n == name]


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: Path, span_names: Sequence[str] = ("rebind", "run_batch")
         ) -> Trace:
    """Read the device timelines and the host thread that holds any of
    ``span_names`` from a recorded trace."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)), span_names)


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns, e.end_ns, e.name) for e in line.events]


def from_profile(pd, span_names: Sequence[str]) -> Trace:
    """Events are read only from the lines that are kept: each device's
    operations and modules, and the host thread that holds any of
    ``span_names``, found by its events' names before they are read."""
    devices, host = [], []
    wanted = set(span_names)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            devices.append(DeviceTimeline(
                plane.name, sorted(lines.get(OPS_LINE, [])),
                sorted(lines.get(MODULES_LINE, []))))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if any(e.name in wanted for e in line.events):
                    host.extend(_events(line))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    # outer events first where two start together, so that they nest
    return Trace(devices, sorted(host, key=lambda ev: (ev[0], -ev[1])))


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Disjoint sorted union of ``intervals``, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (disjoint, sorted) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_ns(dev: DeviceTimeline, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((s, e) for s, e, _ in dev.ops),
                                       lo, hi))


def label_points(host: Sequence[Tuple[float, float, str]],
                 points: Sequence[float]) -> List[Optional[str]]:
    """For each time in ``points``, the name of the innermost host event
    that covers it, as ``outer > inner`` over the nesting (None where the
    thread was outside every event). Host events on one thread nest."""
    order = sorted(range(len(points)), key=lambda i: points[i])
    labels: List[Optional[str]] = [None] * len(points)
    stack: List[Tuple[float, float, str]] = []
    k = 0
    for i in order:
        t = points[i]
        while k < len(host) and host[k][0] <= t:
            ev = host[k]
            while stack and stack[-1][1] <= ev[0]:
                stack.pop()
            stack.append(ev)
            k += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        if stack:
            labels[i] = stack[0][2] if len(stack) == 1 else \
                f"{stack[0][2]} > {stack[-1][2]}"
    return labels


def idle_by_host(trace: Trace, lo: float, hi: float, top: int = 10
                 ) -> List[List]:
    """Idle device time in [lo, hi] per host activity, in seconds averaged
    over the devices, longest first."""
    total: Dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        free = gaps(union(((s, e) for s, e, _ in dev.ops), lo, hi), lo, hi)
        labels = label_points(trace.host, [(s + e) / 2 for s, e in free])
        for (s, e), lab in zip(free, labels):
            total[lab or "outside the harness's spans"] += e - s
    n = max(len(trace.devices), 1)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / n / 1e9] for name, ns in rows]


def op_name(hlo: str) -> str:
    """``%fusion.12 = u32[...] fusion(...), ...`` -> ``%fusion.12 fusion``:
    the instruction and its opcode, without the shapes."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    if rest.startswith("("):                       # a tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{name} {rest.strip().partition('(')[0]}".strip()


def _self_times(ops: Sequence[Tuple[float, float, str]]):
    """(start, end, name, own time) for each op: its duration less that of
    the ops nested in it."""
    out, stack = [], []
    for s, e, name in sorted(ops, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, name, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append(rec)
        out.append(rec)
    return out


def top_ops(trace: Trace, lo: float, hi: float, top: int = 10
            ) -> List[List]:
    """Device time per operation in [lo, hi], each op counted for its own
    time, in seconds averaged over the devices, longest first. An
    operation's name is parsed once per distinct module and name."""
    total: Dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        starts = [m[0] for m in dev.modules]
        mods = [MODULE_HASH.sub("", m[2]) for m in dev.modules]
        labels: Dict[Tuple[str, str], str] = {}
        for s, e, name, own in _self_times(dev.ops):
            if e <= lo or s >= hi:
                continue
            own *= (min(e, hi) - max(s, lo)) / (e - s) if e > s else 0.0
            k = bisect.bisect_right(starts, s) - 1
            mod = mods[k] if k >= 0 and dev.modules[k][1] >= s else "?"
            label = labels.get((mod, name))
            if label is None:
                label = labels[mod, name] = f"{mod}: {op_name(name)}"
            total[label] += own
    n = max(len(trace.devices), 1)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / n / 1e9] for name, ns in rows]
