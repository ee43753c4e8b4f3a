"""Engines' demux: microseconds of ``sim.snapshot`` per stimulus result.
The union of the ``sim.snapshot`` spans in the traced window, over the
window's ``sim.snapshots`` counter (``run.counters``), not over the
spans' number: it reads the same whether the demux opens one span per
stimulus or one per engine call. None where the trace holds no such span
or the window made no result."""


def read(run):
    tr, n = run.trace, run.counters.get("sim.snapshots")
    if tr is None or "sim.snapshot" not in tr.spans or not n:
        return None
    return tr.spans["sim.snapshot"][0] * 1e6 / n
