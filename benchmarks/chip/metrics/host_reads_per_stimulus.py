"""Engines' device-to-host reads per stimulus result: the program's
counters ``sim.host_reads`` over ``sim.snapshots`` (``repro.spans``),
counted over the measured window alone (``run.counters``); set-up's
warm-up calls do not enter them. None where the program keeps no such
counters."""


def read(run):
    c = run.counters
    if not c.get("sim.snapshots"):
        return None
    return c.get("sim.host_reads", 0) / c["sim.snapshots"]
