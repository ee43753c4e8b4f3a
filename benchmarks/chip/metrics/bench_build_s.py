"""Circuit builders (``repro.circuits.build``): the netlist, the stimulus
planes of every seed and their golden values, on the host clock in
set-up."""


def read(run):
    return run.spans.get("bench_build")
