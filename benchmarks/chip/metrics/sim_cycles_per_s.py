"""Simulated RTL cycles per second of wall time: the cycles of every
stimulus of every launch in the window (``sum(r.cycles)``), over the
window's length on the host clock. The window holds whole launches only,
and each launch brings every stimulus's result to the host."""


def read(run):
    if not run.launches or run.window_s <= 0:
        return None
    return run.window_cycles / run.window_s
