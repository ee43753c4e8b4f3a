"""Chip smoke test: the simulator's main path once on a TPU, bit-exact.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # the multi-chip paths, on four chips

Every phase goes through the user's entry points (``repro.sim.compile`` ->
auto-selected engine, and the ``repro.serve`` daemon) on the default 15x15
``HardwareConfig`` at ``scale="full"``, and prints one line with host-compile
seconds (the static-BSP compiler), first-call seconds (trace + XLA compile +
run) and run seconds (a second, warm run) kept apart. One chip runs:

  device   the platform must be "tpu" — there is no CPU fallback;
  b1       all nine circuits at B=1 (MachineEngine) against the NetlistSim
           oracle: finish cycle, exception map {FINISH}, every state reg;
  batch    mc with 1024 seeds through the auto-selected BatchedEngine:
           every element finishes with FINISH only; 8 sampled seeds match
           the oracle bit for bit;
  serve    an in-process SimServer with 64 mixed mc/bc requests: every
           response OK and finished, each equal to the direct run;
  pallas   mc through engine="pallas" (B=1) and the batched Pallas
           backend (B=64), compiled (not interpreted), equal to jnp.

``--four-chips`` runs only the multi-chip paths: the auto-selected
ShardedBatchedEngine on the mc B=1024 batch against the same batch on one
device, and GridEngine on mc B=1 over four devices against the oracle.

The last line of standard output is the JSON result; any failed check
exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCH_SEEDS = 1024
ORACLE_SAMPLES = 8
SERVE_REQUESTS = 64
PALLAS_BATCH = 64


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def line(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def timed(fn, *args, **kwargs):
    t = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t


def same(a, b) -> bool:
    """Bit-exact agreement of two RunResults on every shared probe."""
    return (a.cycles == b.cycles and a.exception_ids == b.exception_ids
            and a.registers == b.registers)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    line("device", platform=info["platform"], kind=repr(info["kind"]),
         count=info["count"], backend=jax.default_backend())
    check(info["platform"] == "tpu",
          f"no TPU: JAX reports platform {info['platform']!r}")
    return info


def oracle_of(program, name: str, scale: str, seed: int):
    """The NetlistSim result of one seed of ``name``, probed on the
    registers ``program`` kept."""
    import repro.sim as sim
    from repro.circuits import build
    bench = build(name, scale, seeds=[seed])
    return sim.Simulation(program=program, circuit=bench.circuit).run(
        bench.n_cycles + sim.CYCLE_SLACK, engine="oracle")


# ---------------------------------------------------------------- phases

def phase_b1(scale: str) -> dict:
    """Every circuit at B=1 on the auto-selected engine vs the oracle;
    returns the jnp results by name."""
    import repro.sim as sim
    from repro.circuits import CIRCUITS
    out = {}
    for name in CIRCUITS:
        s, t_host = timed(sim.compile, name, scale=scale)
        kind = s.engine_kind
        first, t_first = timed(s.run)
        r, t_run = timed(s.run)
        ref = s.run(engine="oracle")
        line("b1", circuit=name, engine=kind, cycles=r.cycles,
             host_compile_s=t_host, first_call_s=t_first, run_s=t_run)
        check(kind == "machine", f"{name}: B=1 selected {kind}")
        check(same(first, r), f"{name}: first and warm runs differ")
        check(r.finished, f"{name}: exceptions {r.exceptions}")
        check(same(r, ref), f"{name}: engine differs from the oracle")
        out[name] = r
    return out


def phase_batch(scale: str, seeds: int = BATCH_SEEDS):
    """mc over ``seeds`` stimuli on the auto-selected batched engine;
    returns (Simulation, results)."""
    import numpy as np
    import repro.sim as sim
    s, t_host = timed(sim.compile, "mc", scale=scale,
                      seeds=list(range(seeds)))
    kind = s.engine_kind
    first, t_first = timed(s.run)
    res, t_run = timed(s.run)
    line("batch", circuit="mc", B=len(res), engine=kind,
         host_compile_s=t_host, first_call_s=t_first, run_s=t_run)
    check(len(res) == seeds, f"batch returned {len(res)} results")
    check(all(r.finished for r in res), "a batch element did not "
          "finish with FINISH only")
    check(all(same(a, b) for a, b in zip(first, res)),
          "first and warm batch runs differ")
    sample = np.random.default_rng(0).choice(seeds, ORACLE_SAMPLES,
                                             replace=False)
    for b in sorted(int(i) for i in sample):
        check(same(res[b], oracle_of(s.program, "mc", scale, b)),
              f"mc seed {b}: batch element differs from the oracle")
    line("batch", oracle_checked=ORACLE_SAMPLES, finished=len(res))
    return s, res


def phase_serve(scale: str, mc_direct) -> None:
    """64 mixed mc/bc requests through the in-process daemon; every
    response must equal the direct run of its seed."""
    import repro.sim as sim
    from repro.serve import BatchPolicy, SimRequest, SimServer
    half = SERVE_REQUESTS // 2
    mc_seeds = list(range(3, 3 + 2 * half, 2))
    bc_seeds = list(range(100, 100 + half))
    reqs = ([SimRequest("mc", scale=scale, seed=s) for s in mc_seeds]
            + [SimRequest("bc", scale=scale, seed=s) for s in bc_seeds])

    async def serve():
        server = SimServer(policy=BatchPolicy(max_batch=SERVE_REQUESTS,
                                              max_wait_s=0.05),
                           cache=False)
        try:
            return (await asyncio.gather(*(server.submit(r) for r in reqs)),
                    server.stats())
        finally:
            await server.close(drain=True)

    (resps, stats), t_total = timed(asyncio.run, serve())
    check(all(r.ok and r.result.finished for r in resps),
          "a served request failed: " + "; ".join(
              f"{r.rid}:{r.status}:{r.error}" for r in resps if not r.ok))
    batches = Counter((r.engine_kind, r.batch) for r in resps)
    line("serve", requests=len(resps), total_s=t_total,
         batches=dict(sorted(batches.items())),
         launches=stats["launch"]["attempts"],
         sessions=stats["sessions"]["counters"]["compiles"],
         engine_compiles=stats["sessions"]["engines"],
         run_s=max(r.run_s for r in resps))
    bc_direct = sim.compile("bc", scale=scale, seeds=bc_seeds).run()
    direct = {("mc", s): mc_direct[s] for s in mc_seeds}
    direct.update({("bc", s): r for s, r in zip(bc_seeds, bc_direct)})
    for req, resp in zip(reqs, resps):
        check(same(resp.result, direct[(req.circuit, req.seed)]),
              f"served {req.circuit} seed {req.seed} differs from the "
              "direct run")
    line("serve", checked_against_direct=len(resps))


def phase_pallas(scale: str, jnp_b1, jnp_batch) -> None:
    """mc on the compiled Pallas kernels (B=1 and B=64) vs jnp."""
    import repro.sim as sim
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret()
    check(not interpret, "Pallas would run in interpret mode")
    s, t_host = timed(sim.compile, "mc", scale=scale)
    eng, t_build = timed(s.engine, "pallas")
    first, t_first = timed(eng.run, s.default_cycles())
    eng.reset()
    r, t_run = timed(eng.run, s.default_cycles())
    line("pallas", circuit="mc", B=1, interpret=interpret,
         host_compile_s=t_host + t_build, first_call_s=t_first, run_s=t_run)
    check(same(first, jnp_b1) and same(r, jnp_b1),
          "Pallas B=1 differs from jnp")
    sb, t_host = timed(sim.compile, "mc", scale=scale,
                       seeds=list(range(PALLAS_BATCH)))
    beng, t_build = timed(sb.engine, "batched", backend="pallas")
    first, t_first = timed(beng.run_batch, sb.default_cycles())
    beng.reset()
    res, t_run = timed(beng.run_batch, sb.default_cycles())
    line("pallas", circuit="mc", B=len(res), interpret=interpret,
         host_compile_s=t_host + t_build, first_call_s=t_first, run_s=t_run)
    check(all(same(a, b) and same(b, c)
              for a, b, c in zip(first, res, jnp_batch[:PALLAS_BATCH])),
          "Pallas B=64 differs from jnp")


def phase_four_chips(scale: str) -> None:
    """Sharded batch vs one device, and GridEngine vs the oracle."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    import repro.sim as sim
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--four-chips needs 4 devices, has "
          f"{len(jax.devices())}")
    s, t_host = timed(sim.compile, "mc", scale=scale,
                      seeds=list(range(BATCH_SEEDS)))
    kind = s.engine_kind
    eng = s.engine()
    first, t_first = timed(eng.run_batch, s.default_cycles())
    eng.reset()
    res, t_run = timed(eng.run_batch, s.default_cycles())
    shards = sorted((str(sh.device), sh.index[0].start or 0)
                    for sh in eng.state.regs.addressable_shards)
    line("sharded", circuit="mc", B=len(res), engine=kind,
         host_compile_s=t_host, first_call_s=t_first, run_s=t_run)
    for dev, start in shards:
        line("sharded", shard_start=start, device=dev)
    check(kind == "sharded", f"4 devices, B={BATCH_SEEDS} selected {kind}")
    check(len({d for d, _ in shards}) == 4, "shards are not on 4 devices")
    one, t_one = timed(s.run, engine="batched")
    line("sharded", reference="batched on one device", first_call_s=t_one)
    check(all(same(a, b) and same(b, c)
              for a, b, c in zip(first, res, one)),
          "sharded batch differs from the one-device batch")
    check(all(r.finished for r in res), "a sharded element did not "
          "finish with FINISH only")

    g, t_host = timed(sim.compile, "mc", scale=scale)
    mesh = Mesh(np.array(devices), ("cores",))
    geng = g.engine(mesh=mesh)
    r, t_first = timed(geng.run, g.default_cycles())
    gdevs = sorted(str(sh.device) for sh in geng.state.regs.addressable_shards)
    line("grid", circuit="mc", B=1, engine=geng.kind, cycles=r.cycles,
         host_compile_s=t_host, first_call_s=t_first, devices=gdevs)
    check(len(set(gdevs)) == 4, "grid state is not on 4 devices")
    check(same(r, g.run(engine="oracle")), "GridEngine differs from the "
          "oracle")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four devices")
    args = ap.parse_args()

    from repro.jax_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    device = device_info()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips("full")
    else:
        b1 = phase_b1("full")
        batch_sim, batch = phase_batch("full")
        phase_serve("full", batch)
        phase_pallas("full", b1["mc"], batch)
    n_cached = sum(1 for p in cache_dir.glob("*") if p.is_file()) \
        if cache_dir.is_dir() else 0
    line("done", total_s=time.perf_counter() - t0, jax_cache=str(cache_dir),
         cache_entries=n_cached)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
