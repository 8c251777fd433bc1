"""Attention: GQA projections, chunked online-softmax (flash-style in pure
JAX — no (S,S) buffer ever materializes), banded sliding-window attention,
and single-token decode against (ring-buffer) KV caches.

Memory discipline is what makes the 32k-prefill dry-run cells fit: full causal
attention runs as a scan over KV chunks carrying (m, l, acc) online-softmax
state; sliding-window layers run banded attention — each Q chunk attends to a
dynamic slice of [chunk_start - window, chunk_end), so compute is O(S·W), not
O(S²).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.basic import apply_rope, dense_init, dtype_of
from repro.sharding.rules import constrain_batch_only

NEG_INF = -1e30


def init_attention(cfg, key, *, cross: bool = False, d_in: int = 0):
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


def qkv(params: Dict, x: jnp.ndarray, cfg, positions=None, *, kv_x=None):
    """Project (+RoPE).  Returns q:(B,S,H,hd), k/v:(B,Skv,KV,hd)."""
    B, S, _ = x.shape
    src = x if kv_x is None else kv_x
    Skv = src.shape[1]
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (src @ params["wk"]).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    v = (src @ params["wv"]).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    if positions is not None and cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_x is None:
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B,S,H,hd) -> (B,S,KV,G,hd) for GQA."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


# ----------------------------------------------------- chunked causal attention
def chunked_attention(q, k, v, cfg, *, causal: bool = True,
                      q_offset: int = 0,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax attention over KV chunks.  q:(B,Sq,H,hd), k/v:(B,Skv,KV,hd).
    Scores are scaled by ``scale``, default 1/sqrt(head_dim)."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    KV = k.shape[2]
    ck = min(cfg.attn_chunk, Skv)
    if Skv % ck:
        ck = math.gcd(Skv, ck) or Skv
    n_kv_chunks = Skv // ck
    scale = 1.0 / math.sqrt(hd) if scale is None else scale

    # hoist the sequence all-gather of K/V: every query position attends over
    # the whole (seq-sharded) KV, so gather ONCE per layer here — otherwise
    # each rematted chunk body re-issues the gather (checkpoint blocks CSE)
    k = constrain_batch_only(k)
    v = constrain_batch_only(v)
    qg = _group(q, KV).astype(jnp.float32) * scale           # (B,Sq,KV,G,hd)
    kc = k.reshape(B, n_kv_chunks, ck, KV, hd)
    vc = v.reshape(B, n_kv_chunks, ck, KV, hd)
    q_pos = q_offset + jnp.arange(Sq)

    @jax.checkpoint  # don't stack (s, p) score buffers across KV chunks in AD
    def body(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        kv_pos = j * ck + jnp.arange(ck)
        s = jnp.einsum("bqkgh,bckh->bqkgc", qg, kj.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]          # (Sq, ck)
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
    if getattr(cfg, "unroll", False):
        carry = init
        for j in range(n_kv_chunks):
            carry, _ = body(carry, (kc_t[j], vc_t[j], jnp.int32(j)))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, init,
                                      (kc_t, vc_t, jnp.arange(n_kv_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


# ------------------------------------------------------------ banded (SWA) attn
def banded_attention(q, k, v, cfg, *, window: int, q_offset: int = 0) -> jnp.ndarray:
    """Sliding-window causal attention: each Q chunk sees [start-W, chunk_end).
    Compute O(S·(W+cq)) — the sub-quadratic mechanism for gemma3/mixtral local
    layers.  q:(B,S,H,hd), k/v:(B,S,KV,hd); W must be a multiple of the chunk."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    cq = min(cfg.attn_chunk, S, max(window, 128))
    if S % cq:
        cq = math.gcd(S, cq)
    n_chunks = S // cq
    W = window
    scale = 1.0 / math.sqrt(hd)
    # pad kv in front with W zeros so the dynamic_slice band is always in range
    # (hoisted gather: see chunked_attention — one all-gather per layer)
    kp = constrain_batch_only(jnp.pad(k, ((0, 0), (W, 0), (0, 0), (0, 0))))
    vp = constrain_batch_only(jnp.pad(v, ((0, 0), (W, 0), (0, 0), (0, 0))))
    qg = _group(q, KV).reshape(B, n_chunks, cq, KV, H // KV, hd)

    @jax.checkpoint  # recompute band scores in bwd instead of stacking them
    def body(_, xs):
        qi, i = xs  # qi: (B,cq,KV,G,hd)
        start = i * cq  # band start in padded coords = (start) → covers [start-W, start+cq)
        kj = jax.lax.dynamic_slice_in_dim(kp, start, W + cq, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(vp, start, W + cq, axis=1)
        s = jnp.einsum("bqkgh,bckh->bqkgc", qi.astype(jnp.float32) * scale,
                       kj.astype(jnp.float32))
        q_pos = q_offset + start + jnp.arange(cq)
        kv_pos = start - W + jnp.arange(W + cq)  # absolute (negatives = padding)
        mask = (q_pos[:, None] >= kv_pos[None, :]) & \
               (q_pos[:, None] - kv_pos[None, :] < W) & (kv_pos[None, :] >= 0)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bqkgc,bckh->bqkgh", p, vj.astype(jnp.float32))
        return None, o

    qg_t = jnp.moveaxis(qg, 1, 0)
    if getattr(cfg, "unroll", False):
        outs = jnp.stack([body(None, (qg_t[i], jnp.int32(i)))[1]
                          for i in range(n_chunks)])
    else:
        _, outs = jax.lax.scan(body, None, (qg_t, jnp.arange(n_chunks)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)
    return out.astype(q.dtype)


# ------------------------------------------------------------------ full (enc)
def full_attention(q, k, v, *, causal: bool,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Small-sequence dense attention (whisper encoder / cross-attn).
    Scores are scaled by ``scale``, default 1/sqrt(head_dim)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    qg = _group(q, KV).astype(jnp.float32)
    qg = qg / math.sqrt(hd) if scale is None else qg * scale
    s = jnp.einsum("bqkgh,bckh->bqkgc", qg, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((Sq, k.shape[1]), bool))
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgc,bckh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# --------------------------------------------------------------------- decode
def decode_attention(q, k_cache, v_cache, kv_positions, pos, *, window: int = 0,
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
    if window:
        valid &= kv_positions > pos - window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgc,bckh->bqkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def cache_update(k_cache, v_cache, kv_positions, k_new, v_new, pos, *, ring: int = 0):
    """Insert one token's k/v at `pos` (ring-buffer slot when ring>0)."""
    C = k_cache.shape[1]
    slot = jnp.mod(pos, ring) if ring else jnp.clip(pos, 0, C - 1)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot, axis=1)
    kv_positions = jax.lax.dynamic_update_slice_in_dim(
        kv_positions, jnp.full((1,), pos, kv_positions.dtype), slot, axis=0)
    return k_cache, v_cache, kv_positions
