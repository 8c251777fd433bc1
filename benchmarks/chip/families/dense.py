"""Decoder-only dense transformer (OLMo-style): pre-norm blocks of causal
multi-head attention with rotary positions and a SwiGLU MLP, LayerNorm with
or without a learned scale, tied embeddings.

Reference: token-parallel float32 forward over a whole sequence, one jitted
layer at a time, with plain softmax attention.  Positions rotate by the
GPT-NeoX convention (the two halves of each head are the pair), as OLMo does.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from families.common import layer_norm, matmul, take_layer


def _check(m: Dict) -> None:
    """The reference computes only what it implements."""
    if (m["norm"] not in ("nonparam_ln", "layernorm")
            or m["mlp_kind"] != "swiglu" or m["act"] != "silu"
            or not m["tie_embeddings"]):
        raise ValueError(f"the dense reference does not implement {m}")


def _rope(x, theta: float):
    """x: (B, S, H, hd), rotated at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, stacked, i, m, quant):
    m = dict(m)
    p = take_layer(stacked, i)
    B, S, _ = x.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = layer_norm(x, p.get("ln1", {}).get("scale"))
    q = matmul(h, p["attn"]["wq"], quant).reshape(B, S, H, hd)
    k = matmul(h, p["attn"]["wk"], quant).reshape(B, S, KV, hd)
    v = matmul(h, p["attn"]["wv"], quant).reshape(B, S, KV, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, S, H * hd)
    x = x + matmul(o, p["attn"]["wo"], quant)
    h = layer_norm(x, p.get("ln2", {}).get("scale"))
    mlp = p["mlp"]
    u = jax.nn.silu(matmul(h, mlp["wg"], quant)) * matmul(h, mlp["wi"], quant)
    return x + matmul(u, mlp["wo"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, embed, final_norm, m, quant):
    m = dict(m)
    x = layer_norm(x, final_norm.get("scale"))
    return matmul(x, embed["table"].T, quant)


def logits(params, tokens, m: Dict, quant: str | None = None) -> jax.Array:
    """Float32 logits (B, S, V) at every position of ``tokens`` (B, S)."""
    _check(m)
    key = tuple(sorted(m.items()))
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    for i in range(m["n_layers"]):
        x = _layer(x, params["layers"], i, key, quant)
    return _head(x, params["embed"], params["final_norm"], key, quant)


def param_count(m: Dict) -> int:
    _check(m)
    d, f, L = m["d_model"], m["d_ff"], m["n_layers"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    norms = 0 if m["norm"] == "nonparam_ln" else (2 * L + 1) * d
    return m["vocab_size"] * d + L * (2 * d * q + 2 * d * kv + 3 * d * f) \
        + norms


def decode_cost(m: Dict, batch: int, ctx: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step needs at ``ctx`` valid cache
    positions (the new token's included): every weight read once, the valid
    keys and values read, the new ones written.  Matmul FLOPs only."""
    d, f, L, V = m["d_model"], m["d_ff"], m["n_layers"], m["vocab_size"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    per_tok = L * (2 * d * q + 2 * d * kv + 3 * d * f) + V * d
    flops = batch * (2 * per_tok + L * 4 * q * ctx)
    wbytes = 2 if m["dtype"] == "bfloat16" else 4
    cache = L * 2 * batch * kv * wbytes * ctx  # ctx - 1 read, 1 written
    return float(flops), float(param_count(m) * wbytes + cache)
