"""Set-up: from the start of the process to the first timed launch (JAX
start, bench build, host compile, stimulus images, XLA compile or cache
load, and the warm-up through every chunk of a launch), on the host
clock."""


def read(run):
    return run.spans.get("setup")
