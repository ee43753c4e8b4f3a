"""Pallas TPU kernel: fused (flash) causal attention.

The §Perf hillclimb on qwen3-0.6b/train_4k showed the memory term is
dominated by materialized [S, S] attention scores, and that XLA-level
chunking cannot remove the operand traffic — only a *fused* kernel can
(scores never leave VMEM). This kernel is that artifact: the online-softmax
formulation with per-query-block running (max, sum, acc) state, streaming
K/V blocks HBM->VMEM via the BlockSpec pipeline.

Block shapes: q block [bq=256, dh<=128] (~128 KiB), one K/V block
[bk=512, dh] (~128 KiB x2) resident at a time, fp32 accumulators
[bq, dh] + [bq] stats — comfortably inside VMEM with double buffering, and
the matmul dims (bq x dh x bk) are MXU-aligned multiples of 128.

Validated bit-close (fp32) / allclose (bf16) against ``ref.flash_ref`` in
interpret mode — tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .ops import resolve_interpret

DEFAULT_BQ = 256
DEFAULT_BK = 512
_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  scale: float):
    """One (batch*head, q-block) grid cell.
    q_ref/o_ref: [1, bq, dh]; k_ref/v_ref: [1, S, dh]."""
    q = q_ref[0].astype(jnp.float32) * scale           # [bq, dh]
    bq, dh = q.shape
    S = k_ref.shape[1]
    iq = pl.program_id(1)
    q_pos = iq * bq + jax.lax.iota(jnp.int32, bq)      # global query rows

    def body(j, carry):
        acc, m, l = carry
        k = jax.lax.dynamic_slice_in_dim(k_ref[0], j * block_k, block_k,
                                         axis=0).astype(jnp.float32)
        v = jax.lax.dynamic_slice_in_dim(v_ref[0], j * block_k, block_k,
                                         axis=0).astype(jnp.float32)
        s = q @ k.T                                    # [bq, bk]
        if causal:
            k_pos = j * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, dh), jnp.float32)
    m0 = jnp.full((bq,), _NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    n_blocks = S // block_k
    if causal:
        # only blocks that intersect the causal triangle of this q block
        n_blocks_live = jnp.minimum(
            (iq + 1) * bq + block_k - 1, S) // block_k
    else:
        n_blocks_live = n_blocks
    acc, m, l = jax.lax.fori_loop(0, n_blocks_live, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q, k, v: [BH, S, dh] -> [BH, S, dh]. S % block_q == S % block_k == 0.
    (GQA callers fold batch x heads into BH and repeat K/V per group.)"""
    BH, S, dh = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (BH, S // block_q)
    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=1.0 / np.sqrt(dh))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
