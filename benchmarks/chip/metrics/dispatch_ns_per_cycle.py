"""Engines' chunk dispatch: nanoseconds of ``sim.dispatch`` (the chunk
launches and their flag syncs) per simulated stimulus-cycle, the union of
the spans in the traced window over the cycles of every stimulus of the
window's launches. None where the trace holds no such span."""


def read(run):
    tr = run.trace
    if tr is None or "sim.dispatch" not in tr.spans or run.window_cycles <= 0:
        return None
    return tr.spans["sim.dispatch"][0] * 1e9 / run.window_cycles
