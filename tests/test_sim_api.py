"""The unified ``repro.sim`` front-end: Engine-protocol parity across all
five executors, facade auto-selection, the on-disk compile cache, and the
circuits.build error surface.

Extends the ``test_engine_fastpath`` patterns one level up: instead of
hand-driving each engine class with its own calling convention, every
engine is driven *through the protocol* and must produce the identical
uniform ``RunResult``.
"""
import numpy as np
import pytest

import repro.sim as sim
from repro.circuits import CIRCUITS, SCALES, build
from repro.core import Circuit, HardwareConfig

HW = HardwareConfig(grid_width=5, grid_height=5)
# three circuits spanning the schedule space: dense compute (mm), sparse
# walkers (mc), cross-core network traffic (noc)
PARITY_NAMES = ["mm", "mc", "noc"]


@pytest.fixture(scope="module")
def sims():
    return {nm: sim.compile(nm, HW, scale="small") for nm in PARITY_NAMES}


def _single_device_mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("cores",))


def _engines(s):
    """Every conforming engine over one compiled Program (the five
    executor classes; pallas rides the Machine adapter)."""
    engines = {
        "machine": s.engine("machine"),
        "seed": s.engine("seed"),
        "isa": s.engine("isa"),
        "batched": s.engine("batched", batch=2),
        "grid": s.engine("grid", mesh=_single_device_mesh()),
    }
    if not s.program.has_global:
        engines["pallas"] = s.engine("pallas")
    return engines


@pytest.mark.parametrize("name", PARITY_NAMES)
def test_engine_adapter_parity(name, sims):
    """The same compiled Program through every engine via the protocol:
    identical registers, outputs, exceptions and finish cycle."""
    s = sims[name]
    n = s.default_cycles()
    results = {}
    for kind, eng in _engines(s).items():
        assert isinstance(eng, sim.Engine)
        results[kind] = eng.run(n)
    ref = results["machine"]
    assert ref.finished, ref.exceptions
    for kind, r in results.items():
        assert r.cycles == ref.cycles, kind
        assert r.exceptions == ref.exceptions, kind
        assert r.registers == ref.registers, kind
        assert r.outputs == ref.outputs, kind
    # ...and the netlist oracle agrees on every probe it shares
    oracle = s.engine("oracle").run(n)
    assert oracle.cycles == ref.cycles
    assert oracle.exception_ids == ref.exception_ids
    assert oracle.registers == ref.registers


@pytest.mark.parametrize("name", PARITY_NAMES)
def test_batched_elements_match_singles(name, sims):
    """run_batch's per-element results equal independent single runs."""
    s = sims[name]
    n = s.default_cycles()
    batched = s.engine("batched", batch=3).run_batch(n)
    single = s.engine("machine").run(n)
    for b, r in enumerate(batched):
        assert r.batch_index == b
        assert r.registers == single.registers
        assert r.exceptions == single.exceptions


def test_outputs_probed_uniformly():
    """Host-visible outputs land in RunResult.outputs on every engine
    (the benches are EXPECT-only, so build a circuit with an output)."""
    c = Circuit("outs")
    cnt = c.reg(16, init=0, name="cnt")
    c.set_next(cnt, cnt + 3)
    c.output("triple", cnt)
    c.finish_when(cnt.eq(30), eid=1)
    s = sim.compile(c, HW)
    for kind in ("machine", "isa", "oracle"):
        r = s.run(64, engine=kind)
        assert r.finished
        assert r.outputs["triple"] == 30, kind


def test_facade_auto_selection():
    s1 = sim.compile("mc", HW, scale="small")
    assert isinstance(s1.engine(), sim.MachineEngine)
    sb = sim.compile("mc", HW, scale="small", seeds=[5, 6])
    assert sb.batch == 2
    eng = sb.engine()
    assert isinstance(eng, sim.BatchedEngine) and eng.batch == 2
    res = sb.run()
    assert isinstance(res, list) and len(res) == 2
    assert all(r.finished for r in res)
    assert isinstance(s1.run(), sim.RunResult)
    assert isinstance(
        sb.engine(mesh=_single_device_mesh()), sim.GridEngine)


def test_seeded_stimuli_differ_but_share_code():
    """seeds= hides the init-plane plumbing: per-seed registers differ at
    stop time while code/luts are the one compiled binary."""
    sb = sim.compile("mc", HW, scale="small", seeds=[5, 6])
    res = sb.run()
    assert res[0].registers != res[1].registers  # price walks differ
    imgs = sb.images()
    assert len(imgs) == 2
    assert not np.array_equal(imgs[0][0], imgs[1][0])


def test_compile_cache_hits_skip_middle_end(tmp_path, monkeypatch):
    """Warm sim.compile must not invoke the compiler at all: the Program
    comes off disk with the cache_hit stats flag set, bit-identically."""
    import repro.sim.facade as facade
    calls = []
    real = facade.compile_circuit
    monkeypatch.setattr(facade, "compile_circuit",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    cold = sim.compile("mc", HW, scale="small", cache=tmp_path)
    assert calls == [1] and not cold.cache_hit
    warm = sim.compile("mc", HW, scale="small", cache=tmp_path)
    assert calls == [1], "cache hit must skip compile_circuit entirely"
    assert warm.cache_hit and warm.program.stats["cache_hit"]
    np.testing.assert_array_equal(warm.program.code, cold.program.code)
    r_cold = cold.run()
    r_warm = warm.run()
    assert r_warm.registers == r_cold.registers
    assert r_warm.exceptions == r_cold.exceptions


def test_cache_key_sensitivity(tmp_path):
    """Different hardware or compiler options never share a cache entry;
    an identical rebuild of the same design does."""
    b1 = build("mc", "small")
    b2 = build("mc", "small")     # independent build, same structure
    k = sim.cache_key(b1.circuit, HW)
    assert sim.cache_key(b2.circuit, HW) == k
    assert sim.cache_key(
        b1.circuit, HardwareConfig(grid_width=4, grid_height=4)) != k
    assert sim.cache_key(b1.circuit, HW, optimize=False) != k
    assert sim.cache_key(b1.circuit, HW, use_luts=False) != k
    b3 = build("mc", "small", n_walkers=2)
    assert sim.cache_key(b3.circuit, HW) != k


def test_cache_entry_from_other_sources_is_a_miss(tmp_path, monkeypatch):
    """An entry that other compiler sources wrote is never served: the
    source digest is part of the key."""
    import repro.sim.cache as cache
    cold = sim.compile("mc", HW, scale="small", cache=tmp_path)
    assert sim.compile("mc", HW, scale="small", cache=tmp_path).cache_hit
    monkeypatch.setattr(cache, "source_digest", lambda: "other sources")
    again = sim.compile("mc", HW, scale="small", cache=tmp_path)
    assert not again.cache_hit
    assert again.meta["cache_key"] != cold.meta["cache_key"]


def test_corrupt_cache_entry_is_a_miss(tmp_path):
    cold = sim.compile("mc", HW, scale="small", cache=tmp_path)
    entry = sim.CompileCache(tmp_path).path(cold.meta["cache_key"])
    entry.write_bytes(b"not an npz")
    again = sim.compile("mc", HW, scale="small", cache=tmp_path)
    assert not again.cache_hit
    assert again.run().finished


def test_build_unknown_name_lists_available():
    with pytest.raises(KeyError) as e:
        build("warp_drive")
    msg = str(e.value)
    for nm in CIRCUITS:
        assert nm in msg
    for sc in SCALES:
        assert sc in msg


def test_build_unknown_scale_lists_valid():
    with pytest.raises(KeyError, match="full"):
        build("mc", scale="enormous")


def test_bench_compile_entry_point():
    s = build("mc", "small").compile(HW)
    assert s.n_cycles == s.bench.n_cycles
    assert s.run().finished


def test_loaded_simulation_needs_cycles_and_has_no_oracle(tmp_path):
    s = sim.compile("mc", HW, scale="small")
    p = tmp_path / "mc.npz"
    s.save(p)
    s2 = sim.load(p)
    with pytest.raises(ValueError, match="cycles"):
        s2.run()
    with pytest.raises(ValueError, match="oracle"):
        s2.engine("oracle")
    assert s2.run(s.default_cycles()).registers == s.run().registers


def test_unknown_engine_kind_rejected():
    s = sim.compile("mc", HW, scale="small")
    with pytest.raises(ValueError, match="unknown engine kind"):
        s.engine("verilator")
