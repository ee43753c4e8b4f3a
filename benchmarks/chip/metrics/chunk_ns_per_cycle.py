"""Engines' chunk program: device busy nanoseconds per simulated
stimulus-cycle in operations of the XLA module ``jit_sim_chunk``, the
union per device in the traced window, summed over the cell's devices as
``device_ns_per_cycle`` sums all operations. None where no device ran
that module."""


def read(run):
    tr = run.trace
    if tr is None or run.window_cycles <= 0 or not any(tr.chunk_busy_ns):
        return None
    return sum(tr.chunk_busy_ns) / run.window_cycles
