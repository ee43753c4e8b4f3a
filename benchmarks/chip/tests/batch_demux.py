"""A demux that reads each engine call's results once for the whole batch,
for the tests to plant in place of the engines' per-stimulus one: what the
benchmark has to take without an edit of its own.

``BatchDemux(eng)`` wraps a batched engine (one chip or sharded) and
passes everything through but ``run_batch``, which runs the engine's
machine, reads ``regs``, ``flags`` and ``counters`` once each through
``repro.spans.to_host`` under one ``sim.snapshot`` span, builds the same
``RunResult``s on the host and counts them as ``sim.snapshots``.
"""
from __future__ import annotations

from repro.sim.engine import _probe_outputs, _probe_registers
from repro.sim.result import RunResult
from repro.spans import count, span, to_host


class BatchDemux:
    def __init__(self, eng):
        self.eng = eng

    def __getattr__(self, name):
        return getattr(self.eng, name)

    def run_batch(self, num_cycles: int):
        eng = self.eng
        eng.state = eng.m.run(eng.state, num_cycles)
        prog = eng.program
        with span("sim.snapshot"):
            regs, flags, cnt = (to_host(x) for x in (
                eng.state.regs, eng.state.flags, eng.state.counters))
            out = []
            for b in range(eng.batch):
                vcycles, ghits, gmisses, stalls = (int(v) for v in cnt[b])
                perf = {"vcycles": vcycles, "ghits": ghits,
                        "gmisses": gmisses, "stall_cycles": stalls,
                        "machine_cycles": vcycles * prog.vcpl + stalls}
                out.append(RunResult(
                    cycles=vcycles,
                    exceptions={c: int(e) for c, e in enumerate(flags[b])
                                if e},
                    perf=perf,
                    registers=_probe_registers(prog, regs[b]),
                    outputs=_probe_outputs(prog, regs[b]),
                    batch_index=b))
        count("sim.snapshots", eng.batch)
        return out
