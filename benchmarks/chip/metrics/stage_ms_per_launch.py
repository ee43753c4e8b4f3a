"""Engines' staging: milliseconds of ``sim.stage`` (an engine's rebind or
reset) per launch, the union of the spans in the traced window over the
window's launches. None where the trace holds no such span."""


def read(run):
    tr = run.trace
    if tr is None or "sim.stage" not in tr.spans or not run.launches:
        return None
    return tr.spans["sim.stage"][0] * 1e3 / len(run.launches)
