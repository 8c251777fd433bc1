"""Shared primitives: norms, RoPE, MLPs, embeddings, init helpers.

Pure-functional JAX: params are nested dicts of arrays; every function takes
(params, inputs) and returns arrays.  Norms/softmax run in fp32 regardless of
the activation dtype (bf16 on TPU).
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp


def dtype_of(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------------- norms
#: every norm's epsilon, fixed here with no option
NORM_EPS = 1e-6


def init_norm(cfg, key):
    if cfg.norm == "nonparam_ln":  # olmo: no learned affine
        return {}
    return {"scale": jnp.ones((cfg.d_model,), dtype_of(cfg))}


def apply_norm(params: Dict, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind in ("layernorm", "nonparam_ln"):
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + NORM_EPS)
    else:  # rmsnorm
        var = (xf**2).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + NORM_EPS)
    if params:
        y = y * params["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ------------------------------------------------------------------------ rope
def rope_frequencies(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, n, head_dim); positions: (S,) or broadcastable."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------------- mlp
#: ``cfg.act``: "gelu" is jax's tanh approximation, "gelu_exact" the erf form
ACTIVATIONS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
               "gelu_exact": functools.partial(jax.nn.gelu, approximate=False)}


def init_mlp(cfg, key):
    dt = dtype_of(cfg)
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        return {"wg": dense_init(ks[0], (d, f), dt),
                "wi": dense_init(ks[1], (d, f), dt),
                "wo": dense_init(ks[2], (f, d), dt)}
    return {"wi": dense_init(ks[0], (d, f), dt),
            "wo": dense_init(ks[1], (f, d), dt)}


def apply_mlp(params: Dict, x: jnp.ndarray, cfg) -> jnp.ndarray:
    act = ACTIVATIONS[cfg.act]
    if "wg" in params:
        return (act(x @ params["wg"]) * (x @ params["wi"])) @ params["wo"]
    return act(x @ params["wi"]) @ params["wo"]


# ------------------------------------------------------------------- embedding
def init_embedding(cfg, key):
    dt = dtype_of(cfg)
    p = {"table": dense_init(key, (cfg.vocab_size, cfg.d_model), dt, scale=0.02)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(jax.random.fold_in(key, 1),
                                  (cfg.d_model, cfg.vocab_size), dt)
    return p


def embed(params: Dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params: Dict, x: jnp.ndarray) -> jnp.ndarray:
    if "unembed" in params:
        return x @ params["unembed"]
    return x @ params["table"].T


# ------------------------------------------------------------------------ loss
def lm_loss_chunked(embed_params: Dict, x: jnp.ndarray, tokens: jnp.ndarray,
                    chunk: int = 512) -> jnp.ndarray:
    """Fused unembed + next-token CE, scanned over sequence chunks so the
    (B,S,V) fp32 logits tensor never materializes — at 262k vocab that buffer
    alone would be 4 GB/chip.  The chunk body is rematerialized in the
    backward pass (jax.checkpoint), trading one extra (B,c,V) matmul for the
    storage."""
    B, S, _ = x.shape
    # next-token shift with a zero-weighted final position keeps S static
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    weights = jnp.concatenate([jnp.ones((B, S - 1), jnp.float32),
                               jnp.zeros((B, 1), jnp.float32)], axis=1)
    c = min(chunk, S)
    while S % c:
        c //= 2
    n = S // c

    @jax.checkpoint
    def body(acc, xs):
        xc, tc, wc = xs
        logits = unembed(embed_params, xc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return acc + ((logz - gold) * wc).sum(), None

    xs = (jnp.moveaxis(x.reshape(B, n, c, -1), 1, 0),
          jnp.moveaxis(targets.reshape(B, n, c), 1, 0),
          jnp.moveaxis(weights.reshape(B, n, c), 1, 0))
    total, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
    return total / weights.sum()
