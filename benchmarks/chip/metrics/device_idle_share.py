"""Device idle share of the traced window, in percent: one minus the busy
union over the window's length, as the mean over the cell's devices."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_ns <= 0 or not tr.busy_ns:
        return None
    idle = [1.0 - b / tr.window_ns for b in tr.busy_ns]
    return 100.0 * sum(idle) / len(idle)
