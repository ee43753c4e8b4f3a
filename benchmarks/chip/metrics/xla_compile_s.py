"""Engines: engine construction plus its first chunk call (``run(1)``),
which traces and compiles the device program or loads it from JAX's
persistent cache, on the host clock in set-up."""


def read(run):
    return run.spans.get("xla_compile")
