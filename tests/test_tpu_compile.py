"""Compile-only rehearsals for a TPU v5e: nothing runs, the chip's compiler
only accepts or refuses the programs of the main path at full-scale shapes.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker running this
file loads the TPU compiler. The persistent compilation cache is off here:
an entry compiled for a described chip cannot be read back without one.
"""
import functools

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.sim as sim
from repro.core.bsp import DEFAULT_CHUNK, Machine
from repro.kernels.ops import exchange_table
from repro.kernels.vcycle import (LANES, SUBLANES, lane_major_program,
                                  round_up, vcycle_chunk_pallas,
                                  vcycle_chunk_pallas_batched, vcycle_pallas)

BATCH = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@functools.lru_cache(maxsize=None)
def _program(name):
    return sim.compile(name, scale="full", cache=False).program


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)


def _chunk_specs(prog, one_chip, batch):
    """Full-scale lane-major kernel operands of ``prog`` as shapes."""
    C, R, S = prog.used_cores, prog.used_reg_count(), prog.spad_init.shape[1]
    Cp = round_up(C, LANES)
    code, luts = lane_major_program(prog.code[:C].transpose(1, 0, 2),
                                    prog.luts[:C], Cp)
    lead = (batch,) if batch else ()
    i32 = np.int32
    return [_spec(one_chip, code.shape, i32), _spec(one_chip, luts.shape, i32),
            _spec(one_chip, exchange_table(prog).shape, i32),
            _spec(one_chip, lead + (round_up(R, SUBLANES), Cp), i32),
            _spec(one_chip, lead + (round_up(S, SUBLANES), Cp), i32),
            _spec(one_chip, lead + (1, Cp), i32),
            _spec(one_chip, (batch or 1,), i32), _spec(one_chip, (1,), i32)]


def _compile_chunk(kernel, prog, one_chip, batch=None):
    f = functools.partial(
        kernel, K=DEFAULT_CHUNK, n_sends=prog.n_sends,
        spad_words=max(prog.spad_init.shape[1], 1), op_set=prog.op_set(),
        num_pro=int(prog.pipe_prologue), interpret=False)
    return jax.jit(f).lower(*_chunk_specs(prog, one_chip, batch)).compile()


@pytest.mark.parametrize("name", ["mc", "mm"])
def test_chunk_kernel_compiles_for_v5e(one_chip, name):
    compiled = _compile_chunk(vcycle_chunk_pallas, _program(name), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["mc", "mm"])
def test_batched_chunk_kernel_compiles_for_v5e(one_chip, name):
    compiled = _compile_chunk(vcycle_chunk_pallas_batched, _program(name),
                              one_chip, batch=BATCH)
    assert "tpu_custom_call" in compiled.as_text()


def test_seed_kernel_compiles_for_v5e(one_chip):
    prog = _program("mc")
    C, R, S = prog.used_cores, prog.used_reg_count(), prog.spad_init.shape[1]
    T, L = prog.code.shape[1], prog.luts.shape[1]
    u32 = np.uint32
    specs = [_spec(one_chip, (T, C, 7), np.int32),
             _spec(one_chip, (C, L, 16), u32), _spec(one_chip, (C, R), u32),
             _spec(one_chip, (C, S), u32), _spec(one_chip, (C,), u32)]
    f = functools.partial(vcycle_pallas, interpret=False)
    compiled = jax.jit(f).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_chunk_compiles_for_v5e(one_chip):
    m = Machine(_program("bc"))
    carry = tuple(_spec(one_chip, x.shape, x.dtype) for x in m.init_state())
    i32 = _spec(one_chip, (), np.int32)
    compiled = m._run_chunk.lower(i32, i32, carry).compile()
    assert compiled.memory_analysis() is not None
