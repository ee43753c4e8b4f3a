"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.row_csv)
and writes JSON rows under results/bench/.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table3 fig9
"""
from __future__ import annotations

import sys
import time

from repro.jax_cache import configure_compile_cache

MODULES = [
    "bench_engine",       # engine Vcycles/sec trajectory (jnp/pallas/isasim)
    "bench_batch",        # batched-stimulus aggregate Vcycles/sec vs B
    "bench_compile",      # middle-end payoff: instrs/VCPL/throughput opt vs off
    "bench_serve",        # serving: coalesced dynamic batching vs B=1 daemon
    "table3_perf",        # Table 3: main performance comparison
    "fig7_scaling",       # Fig 7:  VCPL multicore scaling
    "fig8_global_stall",  # Fig 8:  FIFO/RAM global-stall microbenchmarks
    "fig9_partitioning",  # Fig 9 + Table 4: partitioner ablation
    "fig10_custom_fn",    # Fig 10: custom-instruction ablation
    "table8_compile_time",  # Table 8 / Fig 14: compile-time breakdown
    "fig5_sync_model",    # Fig 5:  sync-cost model
    "table1_grid",        # Table 1 analogue: executor throughput vs grid
    "roofline",           # §Roofline: per (arch x shape) dry-run terms
]


def main() -> None:
    configure_compile_cache()
    want = [a for a in sys.argv[1:] if not a.startswith("-")]
    failures = 0
    for mod in MODULES:
        if want and not any(w in mod for w in want):
            continue
        t0 = time.time()
        print(f"# === {mod} ===", flush=True)
        try:
            m = __import__(f"benchmarks.{mod}", fromlist=["run"])
            m.run()
        except Exception as e:  # noqa
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"# {mod} FAILED: {e}", flush=True)
        print(f"# {mod} took {time.time() - t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
