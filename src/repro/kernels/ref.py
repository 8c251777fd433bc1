"""Pure-jnp oracle for the Pallas CRC32 kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.crc32 import make_table


def crc32_ref(data: jax.Array) -> jax.Array:
    """Reference batch CRC32: same nibble-free byte-table recurrence in plain
    jnp (no pallas), one row per object.  data: (N, W) uint32 → (N,) uint32."""
    table = jnp.asarray(make_table())
    n, w = data.shape

    def word_step(crc, word):
        def byte_step(crc, b):
            byte = (word >> (jnp.uint32(8) * jnp.uint32(b))) & jnp.uint32(0xFF)
            idx = ((crc ^ byte) & jnp.uint32(0xFF)).astype(jnp.int32)
            return (crc >> jnp.uint32(8)) ^ jnp.take(table, idx), None

        for b in range(4):
            crc, _ = byte_step(crc, b)
        return crc, None

    init = jnp.full((n,), 0xFFFFFFFF, jnp.uint32)
    crc, _ = jax.lax.scan(word_step, init, jnp.moveaxis(data, 1, 0))
    return crc ^ jnp.uint32(0xFFFFFFFF)
