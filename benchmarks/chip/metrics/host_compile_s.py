"""Static-BSP compiler (``repro.sim.compile`` with no program cache, as a
designer pays it after every RTL edit), on the host clock in set-up."""


def read(run):
    return run.spans.get("host_compile")
