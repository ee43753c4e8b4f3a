"""Chip benchmark harness: one cell of ``BENCHMARK.json`` per run.

A cell names a configuration, a traffic mix and the chips it needs. Each
lives in a file of its own beside this module, found by its name:

  configs/<config>.json    the deployment: design, scale, the builder's
                           sizes, grid, batch, cycle budget per launch,
                           chips
  traffic/<traffic>.json   how the measured window drives the entry point:
                           what restages a launch (``rebind`` the batch or
                           ``reset`` the engine) and how many Vcycles each
                           engine call runs; ``closed_loop_launches`` reads it
  metrics/<metric>.py      ``read(run)``: one number from a finished run,
                           or None where the run holds nothing to read
  peaks.json               published peaks per ``device_kind``

A run: set-up (JAX start, bench build, host compile, stimulus images, XLA
compile or cache load, a warm-up through every chunk of a launch), then
the measured window of whole launches, then the comparison with the plain
reference (``reference.py``, ``compare.py``), which no metric counts. With
``trace`` the window runs under the JAX profiler and the per-layer metrics
are read from it, from the engines' own spans in it (``engine_spans.py``)
and from the program's counters over the window; without, the end-to-end
metrics.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
SPEC_FILE = REPO / "BENCHMARK.json"
# JAX's persistent compilation cache: a fixed path inside the checkout, so
# that only a checkout's first run compiles and two checkouts share nothing
CACHE_DIR = REPO / ".jax_cache"
SPAN_NAMES = ("rebind", "reset", "run_batch")
RESTAGES = ("rebind", "reset")
# the modelled Manticore's clock (paper §7): a count for reference only
FPGA_HZ = 475e6


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ discovery

def load_spec(path: Path = SPEC_FILE) -> dict:
    return json.loads(Path(path).read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                   + ", ".join(c["name"] for c in spec["workloads"]))


def _data(kind: str, name: str, root: Path) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} {name!r}: {path} is missing")
    return json.loads(path.read_text())


def load_config(name: str, root: Path = HERE) -> dict:
    return _data("configs", name, root)


def load_traffic(name: str, root: Path = HERE) -> dict:
    return _data("traffic", name, root)


def load_metric(name: str, root: Path = HERE) -> ModuleType:
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no metric {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise TypeError(f"metric file {path} has no read(run)")
    return mod


def cell_metrics(spec: dict, trace: bool) -> List[dict]:
    """The metrics a run reports: the end-to-end ones without tracing, the
    per-layer ones with it. A reader that finds nothing to read in a cell
    returns None, and the metric is left out of that cell's line."""
    return spec["per_layer" if trace else "end_to_end"]


def load_peaks(kind: str, root: Path = HERE) -> dict:
    peaks = json.loads((root / "peaks.json").read_text())
    if kind not in peaks["devices"]:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json")
    return peaks["devices"][kind]


# ------------------------------------------------------------ the run

@dataclass
class Launch:
    start: float                    # host clock, s
    end: float
    results: list                   # one RunResult per stimulus
    calls: int = 1                  # engine calls the launch made

    @property
    def cycles(self) -> int:
        return sum(r.cycles for r in self.results)


@dataclass
class TraceSummary:
    busy_ns: List[float]            # per device, inside the traced window
    window_ns: float                # first harness span to the last
    breakdown: dict
    # the engines' ``sim.*`` spans in the window: {name: (seconds, events)}
    spans: Dict[str, Tuple[float, int]] = field(default_factory=dict)
    chunk_busy_ns: List[float] = field(default_factory=list)  # per device


@dataclass
class Run:
    """Everything a metric reader may read."""

    cell: str
    config: dict
    traffic: dict
    spans: Dict[str, float] = field(default_factory=dict)     # set-up, s
    launches: List[Launch] = field(default_factory=list)
    window_s: float = 0.0
    window_compiles: int = 0
    trace: Optional[TraceSummary] = None
    device: dict = field(default_factory=dict)
    counts: Dict[str, float] = field(default_factory=dict)
    # the program's counters (``repro.spans``) over the window alone
    counters: Dict[str, int] = field(default_factory=dict)

    @property
    def window_cycles(self) -> int:
        return sum(ln.cycles for ln in self.launches)


def stimulus_seeds(seed: int, n: int) -> List[int]:
    """``n`` distinct stimulus seeds drawn from the run's ``--seed``."""
    rng = np.random.default_rng(int(seed) % 2 ** 64)
    return [int(s) for s in rng.choice(2 ** 31, size=n, replace=False)]


def use_compile_cache() -> Path:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR


class CompileCounter:
    """Counts the executables JAX makes, compiled or loaded from its
    persistent cache (one monitoring event each)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.n += 1


@contextmanager
def timed(spans: Dict[str, float], name: str):
    t = time.perf_counter()
    yield
    spans[name] = time.perf_counter() - t


def restage(eng, images, traffic: dict) -> None:
    """Put the engine back at cycle 0 as the mix says: ``rebind`` stages
    the batch of stimuli again, as a farm pays for each new batch;
    ``reset`` re-initialises the engine from the stimulus it holds."""
    how = traffic["restage"]
    if how == "rebind":
        eng.rebind(images)
    elif how == "reset":
        eng.reset()
    else:
        raise ValueError(f"restage {how!r} is not one of {RESTAGES}")


def call_lengths(config: dict, traffic: dict) -> List[int]:
    """The Vcycles of each engine call of one launch: the cycle budget in
    calls of ``call_vcycles`` (null: the whole budget in one call)."""
    budget = int(config["budget_vcycles"])
    step = int(traffic.get("call_vcycles") or budget)
    return [step] * (budget // step) + ([budget % step] if budget % step
                                        else [])


def launch(eng, images, config: dict, traffic: dict) -> Launch:
    """One launch: restage, then engine calls until the budget is spent or
    every stimulus has stopped; the last call's results, one per stimulus,
    reach the host."""
    import jax
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(traffic["restage"]):
        restage(eng, images, traffic)
    calls = 0
    with jax.profiler.TraceAnnotation("run_batch"):
        for n in call_lengths(config, traffic):
            res = eng.run_batch(n)
            calls += 1
            if all(r.exceptions for r in res):
                break
    return Launch(t, time.perf_counter(), res, calls)


def closed_loop_launches(run: Run, eng, images, seconds: float,
                         say: Callable[[str], None]) -> None:
    """The window's traffic: one caller, launch after launch, as a
    regression script waits for each; the window ends at the first launch
    boundary after ``seconds`` and holds whole launches only."""
    t0 = time.perf_counter()
    while True:
        ln = launch(eng, images, run.config, run.traffic)
        run.launches.append(ln)
        say(f"[launch] k={len(run.launches) - 1} calls={ln.calls} "
            f"seconds={ln.end - ln.start:.6f} cycles={ln.cycles}")
        if ln.end - t0 >= seconds:
            break
    run.window_s = run.launches[-1].end - t0


def find_devices(chips: int) -> list:
    """The first ``chips`` TPUs, or NoChip."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU, only platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def program_counters() -> Dict[str, int]:
    """The program's counters (``repro.spans``); empty where it keeps
    none."""
    try:
        from repro.spans import counters
    except ImportError:
        return {}
    return counters()


def _summarize_trace(log_dir: Path) -> Optional[TraceSummary]:
    """Busy time, the engines' spans and chunk program, and the breakdown
    of the traced window, which runs from the first harness span to the
    last; None where the trace holds no TPU."""
    import engine_spans
    import tracing
    tr = tracing.load(tracing.find_xplane(log_dir), SPAN_NAMES)
    spans = sorted(sp for name in SPAN_NAMES for sp in tr.spans(name))
    if not spans or not tr.devices:
        return None
    lo, hi = spans[0][0], spans[-1][1]
    return TraceSummary(
        busy_ns=[tracing.busy_ns(d, lo, hi) for d in tr.devices],
        window_ns=hi - lo,
        breakdown={"device_ops": tracing.top_ops(tr, lo, hi),
                   "idle_gaps": tracing.idle_by_host(tr, lo, hi),
                   "idle_by_span": engine_spans.idle_by_span(tr, lo, hi)},
        spans=engine_spans.span_totals(tr, lo, hi),
        chunk_busy_ns=[engine_spans.module_busy_ns(d, lo, hi)
                       for d in tr.devices])


def reference_of(bench, cycles: int, arith: str = "exact"):
    """The plain reference's outcome for every stimulus of ``bench``."""
    import reference
    return reference.simulate(bench.circuit, cycles, bench.reg_planes,
                              bench.mem_planes, arith=arith)


@dataclass
class Prepared:
    """What set-up hands to the window: one engine, warm, and its inputs."""

    run: Run
    sim: object                     # repro.sim.Simulation
    engine: object
    images: tuple
    bench: object
    counter: CompileCounter


def build_bench(config: dict, seed: int):
    """The configuration's bench, one stimulus per seed drawn from
    ``seed``, at the builder's sizes that the configuration states."""
    from repro.circuits import build
    return build(config["design"], config["scale"],
                 seeds=stimulus_seeds(seed, int(config["batch"])),
                 **config.get("params", {}))


def set_up(cell: dict, config: dict, traffic: dict, seed: int,
           devices: Sequence, t_start: float,
           say: Callable[[str], None] = print,
           engine_hook: Optional[Callable] = None) -> Prepared:
    """Build the bench from the seed, compile it, build the auto-selected
    engine and warm every program the window's launches use.

    ``engine_hook(eng)``, where given, may replace the engine before the
    warm-up (the fault tests plant their faults there)."""
    import repro.sim as sim
    from repro.core.isa import HardwareConfig

    if config["chips"] != cell["chips"] or len(devices) != cell["chips"]:
        raise ValueError(f"{cell['name']}: config {config['name']} is for "
                         f"{config['chips']} chips, the cell asks "
                         f"{cell['chips']}, {len(devices)} devices given")
    run = Run(cell["name"], config, traffic)
    counter = CompileCounter()
    with timed(run.spans, "bench_build"):
        bench = build_bench(config, seed)
    with timed(run.spans, "host_compile"):
        s = sim.compile(bench, HardwareConfig(**config["hardware"]),
                        cache=False)
    with timed(run.spans, "images"):
        images = s.images_stacked()
    with timed(run.spans, "xla_compile"):
        eng = s.engine(images=images, devices=list(devices))
        eng.run(1)
    if engine_hook is not None:
        eng = engine_hook(eng)
    with timed(run.spans, "warmup"):
        # every call length of a launch, from a restaged engine; the
        # window's per-stimulus reads use the programs that run(1)
        # compiled for stimulus 0
        restage(eng, images, traffic)
        for n in sorted(set(call_lengths(config, traffic))):
            eng.run(n)
    run.spans["setup"] = time.perf_counter() - t_start
    prog = s.program
    run.counts = {"vcpl": prog.vcpl, "ii": prog.stats.get("vcpl_ii"),
                  "cores": prog.used_cores, "regs": prog.used_reg_count(),
                  "fpga_vcycles_per_s": FPGA_HZ / prog.vcpl}
    say(f"[setup] engine={eng.kind} B={eng.batch} " + " ".join(
        f"{k}_s={v:.6f}" for k, v in run.spans.items()))
    say("[model] counts of the modelled Manticore, not simulator speed: "
        + " ".join(f"{k}={v}" for k, v in run.counts.items()))
    return Prepared(run, s, eng, images, bench, counter)


def execute(cell: dict, config: dict, traffic: dict, seed: int,
            seconds: float, trace: bool, devices: Sequence,
            t_start: float, say: Callable[[str], None] = print,
            engine_hook: Optional[Callable] = None):
    """Set up, run the window and compare; returns (Run, Verdict)."""
    import jax
    import compare

    prep = set_up(cell, config, traffic, seed, devices, t_start, say,
                  engine_hook)
    run = prep.run
    compiles0 = prep.counter.n
    log_dir = Path(tempfile.mkdtemp(prefix="chip-trace-")) if trace else None
    try:
        ctx = jax.profiler.trace(
            str(log_dir), profiler_options=_profile_options()) \
            if trace else nullcontext()
        with ctx:
            before = program_counters()
            closed_loop_launches(run, prep.engine, prep.images, seconds,
                                 say)
            run.counters = {k: v - before.get(k, 0)
                            for k, v in program_counters().items()}
        run.window_compiles = prep.counter.n - compiles0
        say(f"[window] launches={len(run.launches)} seconds="
            f"{run.window_s:.6f} cycles={run.window_cycles} "
            f"compiles={run.window_compiles}")
        stats = [d.memory_stats() or {} for d in devices]
        run.device["memory_peak_bytes"] = max(
            int(st.get("peak_bytes_in_use", 0)) for st in stats)
        if trace:
            run.trace = _summarize_trace(log_dir)
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)

    # the program's state goes before the reference runs
    bench = prep.bench
    prep.engine = prep.sim = prep.images = None
    ref = reference_of(bench, int(config["budget_vcycles"]))
    verdict = compare.judge([ln.results for ln in run.launches], ref,
                            bench.n_cycles)
    return run, verdict


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def result_line(spec: dict, run: Run, verdict, trace: bool,
                device: dict, root: Path = HERE) -> dict:
    """The last line of a run: every field of the result, with the
    numbers compared under ``checks``, last."""
    metrics = {}
    for m in cell_metrics(spec, trace):
        value = load_metric(m["name"], root).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, **run.device)
    out = {"correct": verdict.correct, "attempted": verdict.attempted,
           "failed": verdict.failed, "metrics": metrics, "device": dev}
    if trace and run.trace is not None:
        dev["busy_s"] = sum(run.trace.busy_ns) / len(run.trace.busy_ns) / 1e9
        dev["window_s"] = run.trace.window_ns / 1e9
        out["breakdown"] = run.trace.breakdown
    out["checks"] = verdict.checks()
    return out


def main(argv: Optional[Sequence[str]] = None,
         t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_spec()
    cell = find_cell(spec, args.workload)
    config = load_config(cell["config"])
    traffic = load_traffic(cell["traffic"])
    try:
        devices = find_devices(int(cell["chips"]))
    except NoChip as e:
        print(f"[device] refused: {e}", file=sys.stderr)
        return 4
    import jax
    cache_dir = use_compile_cache()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    peaks = load_peaks(d0.device_kind)
    print(f"[device] platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)} visible={len(jax.devices())} "
          f"jax_cache={cache_dir}", flush=True)

    run, verdict = execute(cell, config, traffic, args.seed, args.seconds,
                           bool(args.trace), devices, t_start,
                           say=lambda s: print(s, flush=True))
    peak = run.device["memory_peak_bytes"]
    print(f"[memory] peak_bytes={peak} "
          f"share_of_hbm={peak / peaks['hbm_bytes']:.6f}", flush=True)
    out = result_line(spec, run, verdict, bool(args.trace), device)
    for name, c in out["checks"].items():
        print(f"[check] {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
