"""Device busy nanoseconds per simulated stimulus-cycle: the union of all
of a device's operations in the traced window, summed over the cell's
devices, over the cycles of every stimulus of the window's launches. The
operations are every device program a launch runs: the chunk program and
the demux's per-stimulus slices (one chip) or gathers (four chips), which
today take most of the busy time. A chunk-program-only reading needs named
scopes inside the program."""


def read(run):
    tr = run.trace
    if tr is None or run.window_cycles <= 0 or not any(tr.busy_ns):
        return None
    return sum(tr.busy_ns) / run.window_cycles
