"""Engines' device-to-host reads per stimulus result: the program's
counters ``sim.host_reads`` over ``sim.snapshots`` (``repro.spans``),
counted over the whole run, set-up's warm-up calls with the window's
launches. A launch reads each stimulus's registers, flags and counters,
and the exception flags once per chunk. None where the program keeps no
such counters."""


def read(run):
    try:
        from repro.spans import counters
    except ImportError:
        return None
    c = counters()
    if not c.get("sim.snapshots"):
        return None
    return c.get("sim.host_reads", 0) / c["sim.snapshots"]
