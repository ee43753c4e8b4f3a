"""Spans and counters at the engines' layer boundaries.

A span is a ``jax.profiler.TraceAnnotation``: it lands in the profiler's
own trace, on the host thread that ran it and on the same clock as the
device's operations, and only while a profile is active
(``jax.profiler.trace``). Without one it costs an object and a check.

Counters are plain ints, process-wide and always on, for operators and
tests to read without a profiler: ``counters()`` returns a copy.

Names (docs/engine.md, "Spans and counters"):

  sim.stage     an engine's rebind or reset: stimuli staged on the device
  sim.dispatch  one engine call's chunk dispatches and per-chunk flag syncs
  sim.snapshot  one stimulus's result brought to a ``RunResult``
  sim.fetch     one device-to-host read (``to_host``)

  sim.snapshots        ``RunResult``s made by the engines
  sim.host_reads       device arrays read to the host
  sim.host_read_bytes  their bytes
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import numpy as np

_counters: Dict[str, int] = {}
_lock = threading.Lock()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A span named ``name``, to be used as a context manager."""
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    with _lock:
        _counters.clear()


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``; a device array is read under ``sim.fetch`` and
    counted, a host array passes through uncounted."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with span("sim.fetch"):
        a = np.asarray(x)
    count("sim.host_reads")
    count("sim.host_read_bytes", a.nbytes)
    return a
