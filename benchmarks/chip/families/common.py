"""Pieces shared by the family references: float32 matmuls at the highest
precision, the fp8 stand-in used by the control, and norms."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn
#: the program's LayerNorm epsilon, fixed in its code (models/layers/basic.py)
#: with no option; the reference follows it, whatever a model publishes
NORM_EPS = 1e-6


def fake_fp8(x: jax.Array, axis=None) -> jax.Array:
    """Round to float8_e4m3fn with an amax scale (per tensor, or per slice
    along ``axis``), back in float32: what an fp8 matmul input holds."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(a: jax.Array, w: jax.Array, quant: str | None) -> jax.Array:
    """a @ w in float32; with quant="fp8" both inputs are first rounded to
    fp8 (activations per row, weights per tensor)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        a, w = fake_fp8(a, axis=-1), fake_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def layer_norm(x: jax.Array, scale=None) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + NORM_EPS)
    return y if scale is None else y * scale.astype(jnp.float32)


def take_layer(stacked, i):
    """Layer ``i`` of a tree of stacked (L, ...) leaves, as float32."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        .astype(jnp.float32), stacked)
