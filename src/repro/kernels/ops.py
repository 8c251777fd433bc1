"""Public jit'd wrappers around the Pallas CRC32 kernel.

The kernel compiles for the TPU by default.  ``interpret=True`` runs the
kernel body in the Pallas interpreter instead; only callers that validate
or time the kernel on a CPU (the tests and the CPU kernel bench) ask for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.crc32 import crc32_pallas


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
