"""Public jit'd wrappers around the Pallas kernels.

The kernels compile for the TPU by default.  ``interpret=True`` runs the
kernel bodies in the Pallas interpreter instead; only callers that validate
or time the kernels on a CPU (the tests and the CPU kernel bench) ask for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.crc32 import crc32_pallas
from repro.kernels.flash_attention import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def crc32_batch(data: jax.Array, block_n: int = 512,
                interpret: bool = False) -> jax.Array:
    """CRC32 of each row of a (N, W) uint32 array."""
    return crc32_pallas(data, block_n=block_n, interpret=interpret)


def crc32_bytes_batch(buffers, *, interpret: bool = False) -> np.ndarray:
    """Host helper: list of equal-length byte strings → uint32 CRCs (pads each
    to whole words with zeros; CRC is over the padded buffer)."""
    n = len(buffers)
    ln = max(len(b) for b in buffers)
    ln_pad = (ln + 3) & ~3
    arr = np.zeros((n, ln_pad), np.uint8)
    for i, b in enumerate(buffers):
        arr[i, : len(b)] = np.frombuffer(b, np.uint8)
    words = arr.view("<u4")
    return np.asarray(crc32_batch(jnp.asarray(words), interpret=interpret))


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """Blocked causal attention.  (B, S, H, hd) with H == KV heads (callers
    repeat KV for GQA) → (B, S, H, hd)."""
    b, s, h, hd = q.shape
    fold = lambda t: jnp.moveaxis(t, 2, 1).reshape(b * h, s, hd)
    o = flash_attention_pallas(fold(q), fold(k), fold(v), causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return jnp.moveaxis(o.reshape(b, h, s, hd), 1, 2)
