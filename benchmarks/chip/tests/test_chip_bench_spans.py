"""CPU tests of the engines' spans as the benchmark reads them: the spans
in a trace recorded around ``rebind`` and ``run_batch``, their reductions
(``engine_spans.py``) on hand-made timelines and on the recorded v5e
trace, which predates the spans, and the readers of the engines'
counters."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(REPO / "src")]

import engine_spans  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402

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


def test_a_recorded_launch_holds_the_engine_spans(farm, tmp_path):
    """A launch traced on the CPU as the harness traces one: the engine's
    spans are events of the thread that ``tracing.from_profile`` picks,
    nested under the harness's spans, one per stage, dispatch, stimulus
    and device-to-host read."""
    import jax
    from repro import spans
    eng, images = farm
    before = spans.counters()
    with jax.profiler.trace(str(tmp_path),
                            profiler_options=harness._profile_options()):
        with jax.profiler.TraceAnnotation("rebind"):
            eng.rebind(images)
        with jax.profiler.TraceAnnotation("run_batch"):
            eng.run_batch(FARM["budget_vcycles"])
    reads = spans.counters()["sim.host_reads"] - before.get(
        "sim.host_reads", 0)
    tr = tracing.load(tracing.find_xplane(tmp_path), harness.SPAN_NAMES)
    (rebind,), (run_batch,) = tr.spans("rebind"), tr.spans("run_batch")
    names = [n for _, _, n in tr.host if n.startswith("sim.")]
    # the stage of rebind holds the stage of its reset
    assert names.count("sim.stage") == 2
    assert names.count("sim.dispatch") == 1
    assert names.count("sim.snapshot") == eng.batch == 4
    assert names.count("sim.fetch") == reads == 3 * 4 + 1

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
    """Each idle gap goes to the innermost ``sim.*`` span at its midpoint,
    past the runtime's own events (``np.asarray`` inside ``sim.fetch``)."""
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


# ------------------------------------------------------------ readers

def test_counter_readers(monkeypatch):
    from repro import spans
    run = harness.Run("c", {}, {})
    monkeypatch.setattr(spans, "_counters", {})
    reads, kb = (harness.load_metric(n) for n in READERS)
    assert reads.read(run) is None and kb.read(run) is None
    spans.count("sim.snapshots", 8)
    spans.count("sim.host_reads", 26)
    spans.count("sim.host_read_bytes", 1_440_000)
    assert reads.read(run) == pytest.approx(3.25)
    assert kb.read(run) == pytest.approx(180.0)
    # a program without the engines' counters: nothing to read
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert reads.read(run) is None and kb.read(run) is None


def test_a_traced_run_reports_the_counters(monkeypatch):
    """A whole traced run of a small farm on the CPU: the engine's spans do
    not disturb the harness's reading of the trace, and the result line
    carries both counter metrics, read over the whole run."""
    import jax
    from repro import spans
    monkeypatch.setattr(spans, "_counters", {})
    cell = {"name": "spans.cell", "chips": 1}
    run, verdict = harness.execute(
        cell, FARM, {"restage": "rebind", "call_vcycles": None},
        2 ** 31 + 7, 0.2, True, jax.devices()[:1],
        harness.time.perf_counter(), lambda s: None)
    assert verdict.correct and run.window_compiles == 0
    out = harness.result_line(harness.load_spec(), run, verdict, True,
                              {"platform": "cpu"})
    c = spans.counters()
    n_results = sum(len(ln.results) for ln in run.launches)
    # set-up's two warm-up calls snapshot stimulus 0 once each
    assert c["sim.snapshots"] == n_results + 2
    metrics = out["metrics"]
    assert metrics["host_reads_per_stimulus"] == {
        "value": c["sim.host_reads"] / c["sim.snapshots"], "unit": "reads"}
    assert 3 < metrics["host_reads_per_stimulus"]["value"] < 4
    assert metrics["host_read_kb_per_stimulus"]["value"] == pytest.approx(
        c["sim.host_read_bytes"] / 1000 / c["sim.snapshots"])
    assert "device_ns_per_cycle" not in metrics
