"""Readings that set the limits of a cell's comparison (not run by the
benchmark's own runs).

  python3 benchmarks/chip/control.py --workload mc-farm.1chip \
      --seeds 11 12 13 ... [--control-seeds 3]

One set-up, as a run makes it, from the first seed. Then, for each seed:
its stimuli are drawn as a run draws them and go through the timed path
(one launch as the cell's mix drives it, on the warm engine; a mix that
restages by ``reset`` gets a set-up of its own per seed, since its engine
holds its stimulus), and the results are compared with the plain
reference. That is the program's reading, which a sound run gives. For the first ``--control-seeds`` seeds the control is
read as well: the reference computed with float32 arithmetic
(``reference.py``), put in the program's place and compared the same way.
A comparison that passes the control is too weak.

It prints one line per seed and reading, and a JSON summary last.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()

    import harness
    import compare

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    try:
        devices = harness.find_devices(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"[device] refused: {e}", file=sys.stderr)
        return 4
    harness.use_compile_cache()
    d0 = devices[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)}", flush=True)
    prep = harness.set_up(cell, config, traffic, args.seeds[0], devices,
                          T_START, say=lambda s: print(s, flush=True))
    cycles = int(config["budget_vcycles"])
    rows = []
    for k, seed in enumerate(args.seeds):
        if k and traffic["restage"] == "reset":
            prep = harness.set_up(cell, config, traffic, seed, devices,
                                  time.perf_counter(), say=lambda s: None)
        bench = harness.build_bench(config, seed)
        images = bench.images_batch(prep.sim.program)
        ln = harness.launch(prep.engine, images, config, traffic)
        ref = harness.reference_of(bench, cycles)
        got = compare.judge([ln.results], ref, bench.n_cycles)
        rows.append({"seed": seed, "reading": "program",
                     "seconds": ln.end - ln.start, **got.numbers})
        print(f"[program] seed={seed} " + " ".join(
            f"{n}={v}" for n, v in got.numbers.items()), flush=True)
        if k < args.control_seeds:
            low = harness.reference_of(bench, cycles, arith="float32")
            ctl = compare.judge([compare.answers(low)], ref, bench.n_cycles)
            rows.append({"seed": seed, "reading": "control",
                         **ctl.numbers})
            print(f"[control] seed={seed} " + " ".join(
                f"{n}={v}" for n, v in ctl.numbers.items()), flush=True)
    summary = {}
    for reading in ("program", "control"):
        mine = [r for r in rows if r["reading"] == reading]
        summary[reading] = {"seeds": len(mine)}
        for n in compare.LIMITS if mine else ():
            summary[reading][n] = {"min": min(r[n] for r in mine),
                                   "max": max(r[n] for r in mine)}
    print(json.dumps({"workload": cell["name"], "limits": compare.LIMITS,
                      "summary": summary, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
