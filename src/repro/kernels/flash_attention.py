"""Blocked causal flash attention as a Pallas TPU kernel.

Grid (batch·heads, n_q_blocks, n_kv_blocks); the last grid dimension is
minor/sequential on TPU, so the online-softmax state (m, l, acc) lives in VMEM
scratch and persists across the KV-block steps of one Q block.  BlockSpecs
tile Q/K/V into (block_q, head_dim) / (block_k, head_dim) VMEM slabs — MXU
dims stay multiples of 128 when head_dim is.

Causal masking is per-element inside the diagonal block; fully-masked KV
blocks are skipped with pl.when (no MXU work issued).

Validated in interpret mode against ref.attention_ref over shape/dtype sweeps;
compiled for TPU v5e at olmo_1b widths in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, block_q: int, block_k: int, causal: bool, scale: float):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = jk * block_k
    # skip blocks that are entirely in the causal future
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T                                       # (bq, bk)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(jk == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q,k,v: (BH, S, hd) → (BH, S, hd).  Same-length self attention."""
    bh, s, hd = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    while s % block_q:
        block_q //= 2
    while s % block_k:
        block_k //= 2
    grid = (bh, s // block_q, s // block_k)
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                               causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # m: running max
            pltpu.VMEM((block_q,), jnp.float32),       # l: running denom
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc: running numerator
        ],
        interpret=interpret,
    )(q, k, v)
