"""CPU tests of the reductions from a profiler trace to busy time, idle
gaps and top operations (``tracing.py``), on hand-made timelines and on a
small trace recorded on a TPU v5e."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(BENCH)]

import engine_spans  # noqa: E402
import tracing  # noqa: E402

RECORDED = HERE / "data" / "tiny_v5e.xplane.pb"


def test_union_merges_overlaps_and_clips():
    got = tracing.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 25)
    assert got == [(1, 3), (5, 12), (20, 25)]
    assert tracing.union([(0, 1)], 2, 3) == []


def test_gaps_fill_the_rest_of_the_window():
    busy = [(1, 3), (5, 12)]
    assert tracing.gaps(busy, 0, 15) == [(0, 1), (3, 5), (12, 15)]
    assert tracing.gaps([], 0, 4) == [(0, 4)]
    assert tracing.gaps([(0, 4)], 0, 4) == []


def test_busy_counts_overlapping_ops_once():
    dev = tracing.DeviceTimeline("/device:TPU:0",
                                 [(0, 10, "a"), (5, 15, "b"), (30, 40, "a")])
    assert tracing.busy_ns(dev, 0, 35) == 15 + 5


def test_label_points_names_the_innermost_host_event():
    host = sorted([(0, 100, "run_batch"), (10, 40, "PjitFunction(chunk)"),
                   (50, 90, "TransferFromDevice"), (120, 150, "rebind")],
                  key=lambda e: (e[0], -e[1]))
    got = tracing.label_points(host, [20, 45, 60, 110, 130, 95])
    assert got == ["run_batch > PjitFunction(chunk)", "run_batch",
                   "run_batch > TransferFromDevice", None, "rebind",
                   "run_batch"]


def _profile():
    def ev(name, start, dur):
        return NS(name=name, start_ns=start, end_ns=start + dur)

    ops = NS(name="XLA Ops", events=[ev("fusion", 10, 20), ev("copy", 60, 10)])
    steps = NS(name="Steps", events=[ev("step", 0, 100)])
    mods = NS(name="XLA Modules", events=[ev("jit_a(42)", 0, 100)])
    dev0 = NS(name="/device:TPU:0", lines=[ops, steps, mods])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[ev("fusion", 0, 100)]), mods])
    py = NS(name="python", events=[ev("rebind", 0, 50), ev("run_batch", 50, 50),
                                   ev("np.asarray", 75, 20)])
    other = NS(name="worker", events=[ev("noise", 0, 100)])
    host = NS(name="/host:CPU", lines=[py, other])
    return NS(planes=[dev1, host, dev0, NS(name="/host:metadata", lines=[])])


def test_from_profile_reads_devices_and_the_span_thread():
    tr = tracing.from_profile(_profile(), ("rebind", "run_batch"))
    assert [d.name for d in tr.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert [n for _, _, n in tr.devices[0].ops] == ["fusion", "copy"]
    assert all(n != "noise" for _, _, n in tr.host)
    assert tr.spans("run_batch") == [(50, 100)]


def test_idle_and_top_ops_average_over_devices():
    tr = tracing.from_profile(_profile(), ("rebind", "run_batch"))
    top = dict(tracing.top_ops(tr, 0, 100))
    assert top == pytest.approx({"jit_a: fusion": (20 + 100) / 2 / 1e9,
                                 "jit_a: copy": 10 / 2 / 1e9})
    idle = dict(tracing.idle_by_host(tr, 0, 100))
    # device 0 idles [0,10) [30,60) in rebind, [70,100) in run_batch
    assert idle == pytest.approx({"rebind": 40 / 2 / 1e9,
                                  "run_batch > np.asarray": 30 / 2 / 1e9})


def test_op_names_drop_the_shapes():
    assert tracing.op_name(
        "%while.132 = (s32[]{:T(128)}, u32[1024,4]{0,1:T(4,128)}) "
        "while((s32[], u32[]) %tuple), condition=%c") == "%while.132 while"
    assert tracing.op_name(
        "%copy.1 = u32[1,194,231]{2,0,1:T(1,128)} copy(u32[1,194,231] %f)"
    ) == "%copy.1 copy"
    assert tracing.op_name("fusion") == "fusion"


def test_nested_ops_count_their_own_time():
    dev = tracing.DeviceTimeline(
        "/device:TPU:0", [(0, 100, "%while.1 = () while()"),
                          (10, 30, "%fusion.2 = u32[] fusion()"),
                          (40, 50, "%fusion.2 = u32[] fusion()")],
        [(0, 100, "jit_chunk(123)")])
    got = dict(tracing.top_ops(tracing.Trace([dev]), 0, 100))
    assert got == pytest.approx({"jit_chunk: %while.1 while": 70 / 1e9,
                                 "jit_chunk: %fusion.2 fusion": 30 / 1e9})


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: three rounds of two small jitted
    programs and a slice under the harness's span names."""
    tr = tracing.load(RECORDED)
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    assert len(tr.devices[0].ops) == 21 and len(tr.devices[0].modules) == 12
    spans = sorted(tr.spans("rebind") + tr.spans("run_batch"))
    assert len(spans) == 6
    lo, hi = spans[0][0], spans[-1][1]
    busy = tracing.busy_ns(tr.devices[0], lo, hi)
    assert 0 < busy < hi - lo
    idle = tracing.idle_by_host(tr, lo, hi)
    assert sum(s for _, s in idle) == pytest.approx((hi - lo - busy) / 1e9)
    assert all(name.startswith(("rebind", "run_batch")) for name, _ in idle)
    top = dict(tracing.top_ops(tr, lo, hi))
    assert "jit__lambda: %add_remainder_fusion fusion" in top
    assert sum(top.values()) == pytest.approx(busy / 1e9)


def test_recorded_reductions_are_unchanged_to_the_digit():
    """The recorded trace's reductions, pinned to the digit: a cheaper
    reduction has to give exactly what the plain one gives."""
    tr = tracing.load(RECORDED)
    assert len(tr.host) == 99
    spans = sorted(tr.spans("rebind") + tr.spans("run_batch"))
    lo, hi = spans[0][0], spans[-1][1]
    assert (lo, hi) == (44719645.0, 150903363.0)
    assert tracing.top_ops(tr, lo, hi) == [
        ["jit__lambda: %add_remainder_fusion fusion", 3.163e-06],
        ["jit__lambda: %copy-done copy-done", 1.506e-06],
        ["jit__lambda: %reduce_sum.7 reduce", 1.052e-06],
        ["jit_dynamic_slice: %dynamic_slice.1 dynamic-slice", 9.45e-07],
        ["jit_dynamic_slice: %copy-start copy-start", 4.1e-08],
        ["jit__lambda: %copy-start copy-start", 2.6e-08],
        ["jit_dynamic_slice: %copy-done copy-done", 8e-09]]
    assert tracing.idle_by_host(tr, lo, hi) == [
        ["run_batch > backend_compile_and_load", 0.100281105],
        ["run_batch > np.asarray(jax.Array)", 0.003375609],
        ["run_batch > DevicePut", 0.002348348],
        ["run_batch > ExecuteReplicated.__call__", 0.00017086],
        ["run_batch > PjitFunction(dynamic_slice)", 3.54e-07],
        ["rebind > PjitFunction(<lambda>)", 3.51e-07],
        ["run_batch > PjitFunction(<lambda>)", 3.5e-07]]
    assert engine_spans.span_totals(tr, lo, hi) == {}
    assert engine_spans.idle_by_span(tr, lo, hi) == [
        [engine_spans.OUTSIDE, 0.106176977]]
    dev = tr.devices[0]
    assert tracing.busy_ns(dev, lo, hi) == 6741.0
    assert engine_spans.module_busy_ns(dev, lo, hi, "jit__lambda") == 5747.0


class _Unread:
    """A line whose events may not be read."""

    def __init__(self, name):
        self.name = name

    @property
    def events(self):
        raise AssertionError(f"line {self.name!r} was read")


def test_from_profile_reads_only_the_lines_it_keeps():
    """A device's lines other than its operations and modules are never
    read."""
    pd = _profile()
    dev0 = next(p for p in pd.planes if p.name == "/device:TPU:0")
    dev0.lines.append(_Unread("Async XLA Ops"))
    tr = tracing.from_profile(pd, ("rebind", "run_batch"))
    assert [len(d.ops) for d in tr.devices] == [2, 1]
    assert [n for _, _, n in tr.host] == ["rebind", "run_batch",
                                          "np.asarray"]
