"""CPU tests of the chip benchmark's harness: the spec, discovery by name,
the metric arithmetic, the window's whole launches and a traced run's
slice of them, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(REPO / "src")]

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


# ------------------------------------------------------------ the spec

def test_spec_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmarks/chip"]
    assert spec["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= spec["run_seconds"] <= 51
    names = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[g]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert len(json.dumps(spec)) < 64 * 1024


def test_spec_configs_match_their_files(spec):
    used = {c["config"] for c in spec["workloads"]}
    for entry in spec["configs"]:
        assert entry["name"] in used
        assert entry["file"].startswith("benchmarks/chip/configs/")
        cfg = harness.load_config(entry["name"])
        assert REPO / entry["file"] == BENCH / "configs" / \
            f"{entry['name']}.json"
        assert cfg["name"] == entry["name"]
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert all(k in cfg for k in entry["reduced"])


def test_spec_cells_report_what_they_must(spec):
    pairs = [(c["config"], c["traffic"]) for c in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [c for c in spec["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 2)
    for cell in spec["workloads"]:
        assert cell["chips"] in (1, 4)
        assert harness.load_config(cell["config"])["chips"] == cell["chips"]
        cfg = harness.load_config(cell["config"])
        traffic = harness.load_traffic(cell["traffic"])
        assert traffic["restage"] in harness.RESTAGES
        assert sum(harness.call_lengths(cfg, traffic)) == \
            cfg["budget_vcycles"]
        e2e = [m["name"] for m in harness.cell_metrics(spec, False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(spec, True)


def test_spec_metrics(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "bound" not in m
        layers.setdefault(m["layer"], []).append(m["name"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        harness.load_metric(m["name"])


# ------------------------------------------------------------ discovery

def _add_cell(tmp_path, config, traffic, metric=None):
    """A copy of the benchmark with one cell added as new files and
    entries only: its configuration, its mix and, where given, a metric
    reader. Returns (spec, root)."""
    root = tmp_path / "chip"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (root / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (root / "traffic" / "dummy-mix.json").write_text(json.dumps(traffic))
    spec = harness.load_spec()
    spec["workloads"].append({"name": "dummy.cell", "config": config["name"],
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "test"})
    if metric is not None:
        name, source = metric
        (root / "metrics" / f"{name}.py").write_text(source)
        spec["per_layer"].append({"name": name, "unit": "launches",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "test",
                                  "moves": "sim_cycles_per_s",
                                  "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_spec(tmp_path / "BENCHMARK.json"), root


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later cell adds files and entries only; the harness finds them,
    and a reader that finds nothing in a cell leaves its metric out."""
    cfg = dict(harness.load_config("mc-farm"), name="dummy-cfg", batch=3)
    reader = ("def read(run):\n"
              "    return len(run.launches) if run.traffic.get('about') "
              "== 'x' else None\n")
    spec, root = _add_cell(tmp_path, cfg, {"restage": "rebind",
                                           "call_vcycles": 70, "about": "x"},
                           ("dummy_launches", reader))
    cell = harness.find_cell(spec, "dummy.cell")
    assert harness.load_config(cell["config"], root)["batch"] == 3
    traffic = harness.load_traffic(cell["traffic"], root)
    assert traffic["about"] == "x"
    assert harness.call_lengths(cfg, traffic) == [70, 70]
    assert "dummy_launches" in [m["name"]
                                for m in harness.cell_metrics(spec, True)]
    verdict = SimpleNamespace(correct=True, attempted=2, failed=0,
                              checks=lambda: {})
    run = _run_with_launches([(0.0, 1.0, [5, 5])])
    run.traffic = traffic
    out = harness.result_line(spec, run, verdict, True, {"platform": "tpu"},
                              root)
    assert out["metrics"]["dummy_launches"] == {"value": 1,
                                                "unit": "launches"}
    run.traffic = harness.load_traffic("farm")
    out = harness.result_line(spec, run, verdict, True, {"platform": "tpu"},
                              root)
    assert "dummy_launches" not in out["metrics"]


LONG_TEST = {"name": "dummy-long", "design": "rv32r", "scale": "small",
             "params": {"n_cores": 4, "n_cycles": 64},
             "hardware": {"grid_width": 5, "grid_height": 5}, "batch": 1,
             "budget_vcycles": 200, "chips": 1}


@pytest.mark.parametrize("config,traffic,engine,calls", [
    # one long stimulus, driven in calls of 16 Vcycles, reset at FINISH
    (LONG_TEST, {"restage": "reset", "call_vcycles": 16}, "machine", 5),
    # a small farm in calls of 20 Vcycles, the batch staged each launch
    (dict(LONG_TEST, name="dummy-farm", design="mc",
          params={"n_walkers": 2, "n_cycles": 24}, batch=4,
          budget_vcycles=34),
     {"restage": "rebind", "call_vcycles": 20}, "batched", 2),
])
def test_a_mix_with_another_driver_is_added_as_files_only(
        tmp_path, config, traffic, engine, calls):
    """A cell whose window drives the entry point differently from the
    farm (another engine, several calls a launch, another restage) runs
    end to end from new data files, and comes out correct."""
    import jax
    spec, root = _add_cell(tmp_path, config, traffic)
    cell = harness.find_cell(spec, "dummy.cell")
    said = []
    run, verdict = harness.execute(
        cell, harness.load_config(cell["config"], root),
        harness.load_traffic(cell["traffic"], root), 2 ** 31 + 99, 0.5,
        False, jax.devices()[:1], harness.time.perf_counter(), said.append)
    assert f"engine={engine} " in said[0]
    assert verdict.correct and verdict.attempted == \
        config["batch"] * len(run.launches)
    assert run.window_compiles == 0 and len(run.launches) >= 1
    assert all(ln.calls == calls for ln in run.launches)
    finish = run.launches[0].results[0].cycles
    assert finish < config["budget_vcycles"]
    assert harness.load_metric("sim_cycles_per_s").read(run) == \
        pytest.approx(config["batch"] * finish * len(run.launches)
                      / run.window_s)


SLICE_FARM = dict(LONG_TEST, name="dummy-farm", design="mc",
                  params={"n_walkers": 2, "n_cycles": 24}, batch=4,
                  budget_vcycles=34)


def _ends_at_the_first_boundary_after(run, seconds):
    t0 = run.launches[-1].end - run.window_s
    ends = [ln.end - t0 for ln in run.launches]
    return ends[-1] >= seconds and all(e < seconds for e in ends[:-1])


def _execute_farm(seconds, trace):
    import jax
    said = []
    run, verdict = harness.execute(
        {"name": "dummy.cell", "chips": 1}, SLICE_FARM,
        {"restage": "rebind", "call_vcycles": None}, 2 ** 31 + 41, seconds,
        trace, jax.devices()[:1], harness.time.perf_counter(), said.append)
    return run, verdict, said


@pytest.fixture(scope="module")
def traced_slice():
    """A traced run asked for the benchmark's 51-second window."""
    return _execute_farm(51.0, True)


def test_a_traced_window_is_a_slice_of_whole_launches(traced_slice):
    """A traced run's window ends at the first launch boundary after
    ``TRACE_SECONDS``, not after ``seconds``, and holds whole launches."""
    run, verdict, said = traced_slice
    assert harness.TRACE_SECONDS < 51.0
    assert len(run.launches) >= 1
    assert _ends_at_the_first_boundary_after(run, harness.TRACE_SECONDS)
    assert run.window_s < 51.0
    assert run.window_compiles == 0 and verdict.correct
    assert f"launches={len(run.launches)} " in said[-1]


def test_a_traced_slice_counts_and_compares_its_own_launches(traced_slice):
    """The counters, the cycles and the comparison cover exactly the
    slice's launches."""
    run, verdict, _ = traced_slice
    results = [r for ln in run.launches for r in ln.results]
    assert run.counters["sim.snapshots"] == len(results) == \
        SLICE_FARM["batch"] * len(run.launches)
    assert run.window_cycles == sum(r.cycles for r in results) > 0
    assert verdict.attempted == len(results) and verdict.failed == 0
    assert harness.load_metric("sim_cycles_per_s").read(run) == \
        pytest.approx(run.window_cycles / run.window_s)


def test_an_untraced_window_still_runs_to_its_seconds():
    """Without tracing the window runs to ``seconds``, here past
    ``TRACE_SECONDS``."""
    seconds = harness.TRACE_SECONDS + 0.5
    run, verdict, _ = _execute_farm(seconds, False)
    assert run.trace is None and verdict.correct
    assert _ends_at_the_first_boundary_after(run, seconds)
    assert verdict.attempted == SLICE_FARM["batch"] * len(run.launches)


@pytest.mark.parametrize("seconds", [0.3, 1.0])
def test_a_traced_window_shorter_than_the_slice_runs_to_its_seconds(
        seconds):
    """Where ``seconds`` is under ``TRACE_SECONDS`` it wins, as in the
    tests' short traced runs."""
    run, verdict, _ = _execute_farm(seconds, True)
    assert _ends_at_the_first_boundary_after(run, seconds)
    assert verdict.correct and len(run.launches) >= 1


def test_unknown_names_are_errors(spec):
    with pytest.raises(KeyError):
        harness.find_cell(spec, "no-such.cell")
    with pytest.raises(KeyError):
        harness.load_config("no-such-config")
    with pytest.raises(KeyError):
        harness.load_traffic("no-such-mix")
    with pytest.raises(KeyError):
        harness.load_metric("no_such_metric")


def test_peaks_table():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes"] == 16e9 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v99")


# ------------------------------------------------------------ arithmetic

def _run_with_launches(launches, spans=None):
    run = harness.Run("mc-farm.1chip", {}, {}, spans=dict(spans or {}))
    for start, end, cycles in launches:
        res = [SimpleNamespace(cycles=c) for c in cycles]
        run.launches.append(harness.Launch(start, end, res))
    if launches:
        run.window_s = launches[-1][1] - launches[0][0]
    return run


def test_rate_counts_every_stimulus_of_every_launch_over_the_window():
    run = _run_with_launches([(10.0, 12.0, [130] * 4), (12.0, 14.5, [130] * 4)])
    rate = harness.load_metric("sim_cycles_per_s").read(run)
    assert rate == pytest.approx(8 * 130 / 4.5)
    assert harness.load_metric("sim_cycles_per_s").read(
        _run_with_launches([])) is None


def test_setup_metrics_read_their_spans():
    spans = {"setup": 30.5, "bench_build": 3.0, "host_compile": 1.5,
             "xla_compile": 4.25}
    run = _run_with_launches([], spans)
    for name, key in (("setup_s", "setup"), ("bench_build_s", "bench_build"),
                      ("host_compile_s", "host_compile"),
                      ("xla_compile_s", "xla_compile")):
        assert harness.load_metric(name).read(run) == spans[key]


def test_device_metrics_from_a_trace_summary():
    run = _run_with_launches([(0.0, 2.0, [100] * 10)])
    ns = harness.load_metric("device_ns_per_cycle")
    idle = harness.load_metric("device_idle_share")
    assert ns.read(run) is None and idle.read(run) is None
    run.trace = harness.TraceSummary(busy_ns=[1.5e9, 0.5e9], window_ns=2e9,
                                     breakdown={})
    assert ns.read(run) == pytest.approx(2e9 / 1000)
    assert idle.read(run) == pytest.approx(100 * (0.25 + 0.75) / 2)
    run.trace.busy_ns = [0.0, 0.0]
    assert ns.read(run) is None


class _FakeEngine:
    """Launches that take ``dt`` seconds of a fake clock each."""

    def __init__(self, clock, dt, B=4, cycles=130):
        self.clock, self.dt, self.B, self.cyc = clock, dt, B, cycles
        self.rebinds = 0

    def rebind(self, images):
        self.rebinds += 1

    def run_batch(self, n):
        self.clock[0] += self.dt
        return [SimpleNamespace(cycles=self.cyc, batch_index=b,
                                exceptions={0: 1}) for b in range(self.B)]


@pytest.mark.parametrize("dt,seconds,want", [(0.3, 1.0, 4), (2.0, 1.0, 1),
                                             (0.25, 1.0, 4)])
def test_window_holds_whole_launches(monkeypatch, dt, seconds, want):
    clock = [100.0]
    monkeypatch.setattr(harness.time, "perf_counter", lambda: clock[0])
    eng = _FakeEngine(clock, dt)
    run = harness.Run("c", {"budget_vcycles": 140},
                      {"restage": "rebind", "call_vcycles": None})
    harness.closed_loop_launches(run, eng, ("imgs",), seconds, lambda s: None)
    assert len(run.launches) == want == eng.rebinds
    assert run.window_s == pytest.approx(want * dt)
    assert run.window_cycles == want * 4 * 130


@pytest.mark.parametrize("step,want", [(None, [140]), (140, [140]),
                                       (32, [32, 32, 32, 32, 12]),
                                       (70, [70, 70])])
def test_call_lengths_spend_the_budget(step, want):
    got = harness.call_lengths({"budget_vcycles": 140},
                               {"call_vcycles": step})
    assert got == want and sum(got) == 140


def test_an_unknown_restage_is_refused():
    with pytest.raises(ValueError):
        harness.restage(None, None, {"restage": "reload"})


def test_stimulus_seeds_are_distinct_and_follow_the_seed():
    big = 2 ** 31 + 977
    a = harness.stimulus_seeds(big, 1024)
    assert a == harness.stimulus_seeds(big, 1024)
    assert len(set(a)) == 1024 and all(0 <= s < 2 ** 31 for s in a)
    assert a != harness.stimulus_seeds(big + 1, 1024)


# ------------------------------------------------------------ refusal

def _run_cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mc-farm.1chip", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    return not any(ln.lstrip().startswith("{")
                   for ln in proc.stdout.splitlines())


def test_refuses_to_run_without_a_tpu():
    proc = _run_cli(REPO)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_in_a_tree_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert _no_result(proc)
