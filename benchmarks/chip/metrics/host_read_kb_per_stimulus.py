"""Engines' device-to-host kilobytes (1,000 bytes) per stimulus result:
the program's counters ``sim.host_read_bytes`` over ``sim.snapshots``
(``repro.spans``), counted over the whole run, set-up's warm-up calls
with the window's launches. None where the program keeps no such
counters."""


def read(run):
    try:
        from repro.spans import counters
    except ImportError:
        return None
    c = counters()
    if not c.get("sim.snapshots"):
        return None
    return c.get("sim.host_read_bytes", 0) / 1000 / c["sim.snapshots"]
