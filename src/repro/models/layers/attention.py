"""Attention: GQA projections, dense attention for short sequences, chunked
online-softmax for long ones (flash-style in pure JAX — no (S,S) buffer ever
materializes: a scan over KV chunks carries (m, l, acc) online-softmax
state), and single-token decode against an append-only KV cache.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.layers.basic import apply_rope, dense_init, dtype_of

NEG_INF = -1e30


def init_attention(cfg, key, *, d_in: int = 0):
    """q/k/v project from ``d_in`` (default d_model) channels; the output
    projection maps back to d_model."""
    dt = dtype_of(cfg)
    d = cfg.d_model
    d_in = d_in or d
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d_in, cfg.q_dim), dt),
        "wk": dense_init(ks[1], (d_in, cfg.kv_dim), dt),
        "wv": dense_init(ks[2], (d_in, cfg.kv_dim), dt),
        "wo": dense_init(ks[3], (cfg.q_dim, d), dt),
    }


def qkv(params: Dict, x: jnp.ndarray, cfg, positions=None):
    """Project (+RoPE).  Returns q:(B,S,H,hd), k/v:(B,S,KV,hd)."""
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (x @ params["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ params["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if positions is not None and cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B,S,H,hd) -> (B,S,KV,G,hd) for GQA."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


# ----------------------------------------------------- chunked causal attention
def chunked_attention(q, k, v, cfg, *,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Causal online-softmax attention over KV chunks.  q:(B,Sq,H,hd),
    k/v:(B,Skv,KV,hd).
    Scores are scaled by ``scale``, default 1/sqrt(head_dim)."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    KV = k.shape[2]
    ck = min(cfg.attn_chunk, Skv)
    if Skv % ck:
        ck = math.gcd(Skv, ck) or Skv
    n_kv_chunks = Skv // ck
    scale = 1.0 / math.sqrt(hd) if scale is None else scale

    qg = _group(q, KV).astype(jnp.float32) * scale           # (B,Sq,KV,G,hd)
    kc = k.reshape(B, n_kv_chunks, ck, KV, hd)
    vc = v.reshape(B, n_kv_chunks, ck, KV, hd)
    q_pos = jnp.arange(Sq)

    @jax.checkpoint  # don't stack (s, p) score buffers across KV chunks in AD
    def body(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        kv_pos = j * ck + jnp.arange(ck)
        s = jnp.einsum("bqkgh,bckh->bqkgc", qg, kj.astype(jnp.float32))
        mask = q_pos[:, None] >= kv_pos[None, :]              # (Sq, ck)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqkgc,bckh->bqkgh", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    G = H // KV
    init = (jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32),
            jnp.zeros((B, Sq, KV, G), jnp.float32),
            jnp.zeros((B, Sq, KV, G, hd), jnp.float32))
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    (m, l, acc), _ = jax.lax.scan(body, init,
                                  (kc_t, vc_t, jnp.arange(n_kv_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


# ----------------------------------------------------------------------- dense
def full_attention(q, k, v, *,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Small-sequence dense causal attention.
    Scores are scaled by ``scale``, default 1/sqrt(head_dim)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    qg = _group(q, KV).astype(jnp.float32)
    qg = qg / math.sqrt(hd) if scale is None else qg * scale
    s = jnp.einsum("bqkgh,bckh->bqkgc", qg, k.astype(jnp.float32))
    mask = jnp.tril(jnp.ones((Sq, k.shape[1]), bool))
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgc,bckh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# --------------------------------------------------------------------- decode
def decode_attention(q, k_cache, v_cache, kv_positions, pos, *,
                     scale: Optional[float] = None):
    """One-token attention against a cache.
    q: (B,1,H,hd); caches: (B,C,KV,hd); kv_positions: (C,) absolute positions
    (-1 = empty slot); pos: scalar current position.  Scores are scaled by
    ``scale``, default 1/sqrt(head_dim)."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    qg = _group(q, KV).astype(jnp.float32)
    qg = qg / math.sqrt(hd) if scale is None else qg * scale
    s = jnp.einsum("bqkgh,bckh->bqkgc", qg, k_cache.astype(jnp.float32))
    valid = (kv_positions >= 0) & (kv_positions <= pos)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgc,bckh->bqkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def cache_update(k_cache, v_cache, kv_positions, k_new, v_new, pos):
    """Insert one token's k/v at slot `pos`."""
    C = k_cache.shape[1]
    slot = jnp.clip(pos, 0, C - 1)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot, axis=1)
    kv_positions = jax.lax.dynamic_update_slice_in_dim(
        kv_positions, jnp.full((1,), pos, kv_positions.dtype), slot, axis=0)
    return k_cache, v_cache, kv_positions
