"""Engines' device-to-host kilobytes (1,000 bytes) per stimulus result:
the program's counters ``sim.host_read_bytes`` over ``sim.snapshots``
(``repro.spans``), counted over the measured window alone
(``run.counters``); set-up's warm-up calls do not enter them. None where
the program keeps no such counters."""


def read(run):
    c = run.counters
    if not c.get("sim.snapshots"):
        return None
    return c.get("sim.host_read_bytes", 0) / 1000 / c["sim.snapshots"]
