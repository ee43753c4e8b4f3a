"""CPU tests of the engines' spans as the benchmark reads them: the spans
in a trace recorded around ``rebind`` and ``run_batch``, their reductions
(``engine_spans.py``) on hand-made timelines and on the recorded v5e
trace, which predates the spans, their wiring into the harness's trace
summary, and the readers of the engines' spans and window counters.

What the readers rely on is held whichever way the demux reads: the
engines' per-stimulus one, and a demux that reads the whole batch at once
(``batch_demux.BatchDemux``)."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parents[1]
sys.path[:0] = [str(HERE), str(BENCH), str(REPO / "src")]

import engine_spans  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402
from batch_demux import BatchDemux  # noqa: E402

RECORDED = HERE / "data" / "tiny_v5e.xplane.pb"
FARM = {"name": "spans-farm", "design": "mc", "scale": "small",
        "params": {"n_walkers": 2, "n_cycles": 24},
        "hardware": {"grid_width": 5, "grid_height": 5}, "batch": 4,
        "budget_vcycles": 34, "chips": 1}
READERS = ("host_reads_per_stimulus", "host_read_kb_per_stimulus")


@pytest.fixture(scope="module")
def farm():
    import repro.sim as sim
    from repro.core.isa import HardwareConfig
    bench = harness.build_bench(FARM, 2 ** 31 + 5)
    s = sim.compile(bench, HardwareConfig(**FARM["hardware"]), cache=False)
    images = s.images_stacked()
    return s.engine(images=images), images


def _recorded_launch(eng, images, tmp_path):
    """A launch traced on the CPU as the harness traces one: the engine's
    spans are events of the thread that ``tracing.from_profile`` picks,
    nested under the harness's spans: a stage per restage, a dispatch per
    call, a fetch per counted device-to-host read, and the demux's
    snapshots, which count every result once."""
    import jax
    from repro import spans
    before = spans.counters()
    with jax.profiler.trace(str(tmp_path),
                            profiler_options=harness._profile_options()):
        with jax.profiler.TraceAnnotation("rebind"):
            eng.rebind(images)
        with jax.profiler.TraceAnnotation("run_batch"):
            results = eng.run_batch(FARM["budget_vcycles"])
    after = spans.counters()
    reads = after["sim.host_reads"] - before.get("sim.host_reads", 0)
    tr = tracing.load(tracing.find_xplane(tmp_path), harness.SPAN_NAMES)
    (rebind,), (run_batch,) = tr.spans("rebind"), tr.spans("run_batch")
    names = [n for _, _, n in tr.host if n.startswith("sim.")]
    # the stage of rebind holds the stage of its reset
    assert names.count("sim.stage") == 2
    assert names.count("sim.dispatch") == 1
    assert names.count("sim.snapshot") >= 1
    assert names.count("sim.fetch") == reads >= 1
    assert after["sim.snapshots"] - before.get("sim.snapshots", 0) == \
        eng.batch == len(results) == 4

    def inside(name, outer):
        return all(outer[0] <= s and e <= outer[1]
                   for s, e in tr.spans(name))

    assert inside("sim.stage", rebind)
    assert all(inside(n, run_batch)
               for n in ("sim.dispatch", "sim.snapshot", "sim.fetch"))
    lo, hi = rebind[0], run_batch[1]
    totals = engine_spans.span_totals(tr, lo, hi)
    assert set(totals) == {"sim.stage", "sim.dispatch", "sim.snapshot",
                           "sim.fetch"}
    assert totals["sim.stage"][1] == 2
    # nested events of one name count once: the outer stage alone
    outer_stage = max(e - s for s, e in tr.spans("sim.stage"))
    assert totals["sim.stage"][0] == pytest.approx(outer_stage / 1e9)
    assert totals["sim.snapshot"][0] <= (run_batch[1] - run_batch[0]) / 1e9
    return names, reads


def test_a_recorded_launch_holds_the_engine_spans(farm, tmp_path):
    eng, images = farm
    _recorded_launch(eng, images, tmp_path)


def test_a_recorded_batch_demux_launch_holds_the_engine_spans(farm,
                                                              tmp_path):
    eng, images = farm
    names, reads = _recorded_launch(BatchDemux(eng), images, tmp_path)
    # one snapshot for the call: the registers, flags and counters of the
    # batch, and the one chunk's flag sync
    assert names.count("sim.snapshot") == 1
    assert reads == 3 + 1


def _trace():
    """Two devices over [0, 100): chunk and gather modules; host spans of
    the harness with the engine's spans nested inside."""
    host = sorted([(0, 20, "rebind"), (2, 18, "sim.stage"),
                   (5, 15, "sim.stage"), (20, 100, "run_batch"),
                   (22, 40, "sim.dispatch"), (30, 34, "sim.fetch"),
                   (45, 70, "sim.snapshot"), (50, 60, "sim.fetch"),
                   (52, 58, "np.asarray"), (70, 95, "sim.snapshot")],
                  key=lambda ev: (ev[0], -ev[1]))
    dev0 = tracing.DeviceTimeline(
        "/device:TPU:0",
        [(24, 30, "%fusion.1 = fusion()"), (26, 28, "%copy = copy()"),
         (62, 66, "%gather = gather()")],
        [(23, 31, "jit_sim_chunk(77)"), (61, 67, "jit_gather(5)")])
    dev1 = tracing.DeviceTimeline(
        "/device:TPU:1", [(24, 32, "%fusion.1 = fusion()")],
        [(23, 33, "jit_sim_chunk(77)")])
    return tracing.Trace([dev0, dev1], host)


def test_span_totals_take_the_union_per_name_in_the_window():
    got = engine_spans.span_totals(_trace(), 10, 100)
    assert got == {"sim.stage": (pytest.approx(8e-9), 2),
                   "sim.dispatch": (pytest.approx(18e-9), 1),
                   "sim.fetch": (pytest.approx(14e-9), 2),
                   "sim.snapshot": (pytest.approx(50e-9), 2)}
    assert engine_spans.span_totals(_trace(), 96, 100) == {}


def test_chunk_busy_counts_only_the_chunk_module():
    dev0, dev1 = _trace().devices
    assert engine_spans.module_busy_ns(dev0, 0, 100) == 6
    assert engine_spans.module_busy_ns(dev0, 0, 100, "jit_gather") == 4
    assert engine_spans.module_busy_ns(dev1, 0, 28) == 4
    assert engine_spans.module_busy_ns(dev1, 0, 100, "jit_other") == 0


def test_idle_is_put_down_to_the_innermost_engine_span():
    """Idle time goes to the innermost ``sim.*`` span that covers it, past
    the runtime's own events (``np.asarray`` inside ``sim.fetch``)."""
    dev0 = tracing.DeviceTimeline("/device:TPU:0", [
        (1, 24, "a"), (26, 31, "a"), (33, 52, "a"), (58, 75, "a"),
        (80, 100, "a")])
    dev1 = tracing.DeviceTimeline("/device:TPU:1",
                                  [(0, 10, "a"), (14, 100, "a")])
    tr = tracing.Trace([dev0, dev1], _trace().host)
    got = dict(engine_spans.idle_by_span(tr, 0, 100))
    # device 0 idles [0,1) [24,26) [31,33) [52,58) [75,80); device 1 [10,14)
    assert got == pytest.approx({
        engine_spans.OUTSIDE: 1 / 2 / 1e9, "sim.dispatch": 2 / 2 / 1e9,
        "sim.fetch": (2 + 6) / 2 / 1e9, "sim.snapshot": 5 / 2 / 1e9,
        "sim.stage": 4 / 2 / 1e9})
    assert list(got)[0] == "sim.fetch"
    # a gap across several spans is split among them, not named whole by
    # the span at its midpoint (38, in sim.dispatch)
    dev = tracing.DeviceTimeline("/device:TPU:0", [(0, 28, "a"),
                                                   (48, 100, "a")])
    tr = tracing.Trace([dev], _trace().host)
    assert dict(engine_spans.idle_by_span(tr, 0, 100)) == pytest.approx({
        "sim.dispatch": (2 + 6) / 1e9, "sim.fetch": 4 / 1e9,
        engine_spans.OUTSIDE: 5 / 1e9, "sim.snapshot": 3 / 1e9})


def test_innermost_segments_of_nested_spans():
    events = [ev for ev in _trace().host if ev[2].startswith("sim.")]
    assert engine_spans.innermost(events) == [
        (2, 5, "sim.stage"), (5, 15, "sim.stage"), (15, 18, "sim.stage"),
        (22, 30, "sim.dispatch"), (30, 34, "sim.fetch"),
        (34, 40, "sim.dispatch"), (45, 50, "sim.snapshot"),
        (50, 60, "sim.fetch"), (60, 70, "sim.snapshot"),
        (70, 95, "sim.snapshot")]
    # an event that outlasts the one it started in ends with it
    assert engine_spans.innermost([(0, 10, "a"), (5, 12, "b")]) == [
        (0, 5, "a"), (5, 10, "b")]
    assert engine_spans.innermost([]) == []


def test_the_recorded_trace_has_no_engine_spans():
    """The v5e trace predates the engine's spans and the chunk program's
    name: each reduction finds nothing, and says so."""
    tr = tracing.load(RECORDED)
    spans = sorted(tr.spans("rebind") + tr.spans("run_batch"))
    lo, hi = spans[0][0], spans[-1][1]
    assert engine_spans.span_totals(tr, lo, hi) == {}
    assert engine_spans.module_busy_ns(tr.devices[0], lo, hi) == 0
    idle = engine_spans.idle_by_span(tr, lo, hi)
    assert [name for name, _ in idle] == [engine_spans.OUTSIDE]
    busy = tracing.busy_ns(tr.devices[0], lo, hi)
    assert idle[0][1] == pytest.approx((hi - lo - busy) / 1e9)


def test_the_trace_summary_carries_the_engine_readings(monkeypatch):
    """``_summarize_trace`` on a hand-made trace with the engine's spans
    and chunk program: the window runs from ``rebind`` to the end of
    ``run_batch``, and the summary carries their reductions."""
    tr = _trace()
    monkeypatch.setattr(tracing, "find_xplane", lambda log_dir: log_dir)
    monkeypatch.setattr(tracing, "load", lambda path, names: tr)
    got = harness._summarize_trace(Path("unused"))
    assert got.window_ns == 100
    assert got.spans == engine_spans.span_totals(tr, 0, 100)
    assert got.spans["sim.snapshot"] == (pytest.approx(50e-9), 2)
    assert got.chunk_busy_ns == [6, 8]
    assert got.breakdown["idle_by_span"] == \
        engine_spans.idle_by_span(tr, 0, 100)
    assert {"device_ops", "idle_gaps"} <= set(got.breakdown)


def test_the_recorded_trace_summary_reads_no_engine_spans():
    """The recorded v5e trace predates the spans and the chunk program's
    name: the summary has a chunk reading of 0 for each device and no
    spans, so the engine readers read nothing."""
    got = harness._summarize_trace(RECORDED.parent)
    n_devices = len(tracing.load(RECORDED).devices)
    assert got.chunk_busy_ns == [0] * n_devices and n_devices >= 1
    assert got.spans == {}
    assert [name for name, _ in got.breakdown["idle_by_span"]] == \
        [engine_spans.OUTSIDE]
    run = _reader_run(got.spans, got.chunk_busy_ns)
    for name in SPAN_READERS:
        assert harness.load_metric(name).read(run) is None


# ------------------------------------------------------------ readers

SPAN_READERS = ("demux_us_per_stimulus", "stage_ms_per_launch",
                "dispatch_ns_per_cycle", "chunk_ns_per_cycle")
SPANS = {"sim.stage": (0.5, 4), "sim.dispatch": (0.25, 2),
         "sim.snapshot": (30.0, 2048), "sim.fetch": (4.0, 6150)}


def _reader_run(spans=None, chunk_busy_ns=(), snapshots=2048):
    """Two launches of 1,024 stimuli of 130 cycles, traced."""
    run = harness.Run("c", {}, {}, counters={"sim.snapshots": snapshots})
    res = [SimpleNamespace(cycles=130)] * 1024
    run.launches = [harness.Launch(0.0, 16.0, res),
                    harness.Launch(16.0, 32.0, res)]
    run.trace = harness.TraceSummary(
        busy_ns=[1e9], window_ns=32e9, breakdown={},
        spans=dict(spans or {}), chunk_busy_ns=list(chunk_busy_ns))
    return run


@pytest.mark.parametrize("name,want,span", [
    # the snapshots' union over the window's results, not over its spans
    ("demux_us_per_stimulus", 30.0 * 1e6 / 2048, "sim.snapshot"),
    ("stage_ms_per_launch", 0.5 * 1e3 / 2, "sim.stage"),
    ("dispatch_ns_per_cycle", 0.25 * 1e9 / (2048 * 130), "sim.dispatch"),
    ("chunk_ns_per_cycle", (0.1e9 + 0.3e9) / (2048 * 130), None),
])
def test_engine_readers(name, want, span):
    reader = harness.load_metric(name)
    run = _reader_run(SPANS, [0.1e9, 0.3e9])
    assert reader.read(run) == pytest.approx(want)
    # a demux that opens one span per call reads the same
    one_span = dict(SPANS, **{"sim.snapshot": (30.0, 2)})
    assert reader.read(_reader_run(one_span, [0.1e9, 0.3e9])) == \
        pytest.approx(want)
    # no trace; a trace without the reader's span, or no chunk module
    run.trace = None
    assert reader.read(run) is None
    others = {k: v for k, v in SPANS.items() if k != span}
    chunk = [0, 0] if span is None else [0.1e9, 0.3e9]
    assert reader.read(_reader_run(others, chunk)) is None


def test_the_demux_reader_reads_nothing_without_results():
    reader = harness.load_metric("demux_us_per_stimulus")
    assert reader.read(_reader_run(SPANS, snapshots=0)) is None
    run = _reader_run(SPANS)
    run.counters = {}
    assert reader.read(run) is None


def test_counter_readers(monkeypatch):
    from repro import spans
    run = harness.Run("c", {}, {})
    reads, kb = (harness.load_metric(n) for n in READERS)
    assert reads.read(run) is None and kb.read(run) is None
    run.counters = {"sim.snapshots": 8, "sim.host_reads": 26,
                    "sim.host_read_bytes": 1_440_000}
    # the window's counters, not the process's
    monkeypatch.setattr(spans, "_counters", {"sim.snapshots": 1,
                                             "sim.host_reads": 99})
    assert reads.read(run) == pytest.approx(3.25)
    assert kb.read(run) == pytest.approx(180.0)
    assert harness.program_counters() == spans.counters()
    # a program without the engines' counters: the harness hands over
    # none, and there is nothing to read
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert harness.program_counters() == {}
    run.counters = {}
    assert reads.read(run) is None and kb.read(run) is None


def _traced_run(monkeypatch, hook=None, batch=FARM["batch"]):
    """A whole traced run of a small farm on the CPU: the engine's spans do
    not disturb the harness's reading of the trace, the run carries the
    program's counters over the window alone, and the result line carries
    both counter metrics, each its ratio of the window's counters."""
    import jax
    from repro import spans
    monkeypatch.setattr(spans, "_counters", {})
    cell = {"name": "spans.cell", "chips": 1}
    run, verdict = harness.execute(
        cell, dict(FARM, batch=batch),
        {"restage": "rebind", "call_vcycles": None},
        2 ** 31 + 7, 0.2, True, jax.devices()[:1],
        harness.time.perf_counter(), lambda s: None, hook)
    assert verdict.correct and run.window_compiles == 0
    out = harness.result_line(harness.load_spec(), run, verdict, True,
                              {"platform": "cpu"})
    c, w = spans.counters(), run.counters
    assert w["sim.snapshots"] == sum(len(ln.results) for ln in run.launches)
    # set-up's warm-up calls read too, outside the window
    assert c["sim.host_reads"] > w["sim.host_reads"] > 0
    metrics = out["metrics"]
    assert metrics["host_reads_per_stimulus"] == {
        "value": w["sim.host_reads"] / w["sim.snapshots"], "unit": "reads"}
    assert metrics["host_read_kb_per_stimulus"] == {
        "value": w["sim.host_read_bytes"] / 1000 / w["sim.snapshots"],
        "unit": "KB"}
    assert all(metrics[n]["value"] > 0 for n in READERS)
    # no TPU in a CPU trace: no device or engine-span reading
    assert run.trace is None
    assert not {"device_ns_per_cycle", *SPAN_READERS} & set(metrics)
    return metrics


def test_a_traced_run_reports_the_counters(monkeypatch):
    _traced_run(monkeypatch)


def test_a_traced_run_takes_a_batch_demux(monkeypatch):
    """The same run with a demux that reads each call's results once for
    the whole batch: correct, nothing compiles in the window, and, at 8
    stimuli, fewer than one read per stimulus (three reads of the batch
    and a flag sync per launch)."""
    metrics = _traced_run(monkeypatch, BatchDemux, batch=8)
    assert metrics["host_reads_per_stimulus"]["value"] < 1
