"""RWKV6 "Finch" blocks: time-mix with data-dependent per-channel decay and
channel-mix FFN.

Recurrence per head (k,r ∈ R^hd, v ∈ R^hd, decay w_t ∈ (0,1)^hd data-dependent):
    y_t = r_t · (S_{t-1} + (u ∘ k_t) v_tᵀ)
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

TPU adaptation: chunked linear attention — within a chunk the pairwise decay
factorizes as exp(lw_i − lw_j) (lw = cumulative log-decay), so intra-chunk work
is two matmuls with decay-scaled r'/k'; a short scan carries S across chunks.
Chunks stay small (default 64) so exp(lw_ref − lw_j) cannot overflow fp32.

Simplification noted in DESIGN.md: token-shift uses the static-mix (RWKV5-style
mu) interpolation; the decay keeps its RWKV6 data-dependent LoRA.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.basic import apply_norm, dense_init, dtype_of


def init_rwkv_block(cfg, key):
    dt = dtype_of(cfg)
    d, f = cfg.d_model, cfg.d_ff
    H, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    ks = jax.random.split(key, 10)
    lora = 64
    return {
        "tm": {  # time mix
            "mu": 0.5 * jnp.ones((5, d), dt),   # r,k,v,w,g static shift mixes
            "wr": dense_init(ks[0], (d, d), dt),
            "wk": dense_init(ks[1], (d, d), dt),
            "wv": dense_init(ks[2], (d, d), dt),
            "wg": dense_init(ks[3], (d, d), dt),
            "wo": dense_init(ks[4], (d, d), dt),
            "w0": -6.0 * jnp.ones((d,), jnp.float32),     # base log-log decay
            "w_lora_a": dense_init(ks[5], (d, lora), dt),
            "w_lora_b": dense_init(ks[6], (lora, d), dt, scale=0.01),
            "u": dense_init(ks[7], (H, hd), jnp.float32, scale=0.5),
            "ln": jnp.ones((d,), dt),
        },
        "cm": {  # channel mix
            "mu": 0.5 * jnp.ones((2, d), dt),
            "wr": dense_init(jax.random.fold_in(key, 99), (d, d), dt),
            "wk": dense_init(ks[8], (d, f), dt),
            "wv": dense_init(ks[9], (f, d), dt),
        },
    }


def _shift(x, last=None):
    """Token shift: x_{t-1} (zeros / carried `last` at t=0)."""
    if last is None:
        last = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def wkv_chunked(r, k, v, w, u, h0, chunk: int):
    """Chunked WKV.  r,k,w: (B,S,H,hd); v: (B,S,H,hd); u: (H,hd);
    h0: (B,H,hd,hd).  Returns y: (B,S,H,hd), h_last."""
    B, S, H, hd = r.shape
    c = min(chunk, S)
    while S % c:
        c //= 2
    n = S // c
    rs, ks_, vs, ws = (a.reshape(B, n, c, H, hd).astype(jnp.float32)
                       for a in (r, k, v, w))
    lw = jnp.cumsum(jnp.log(ws), axis=2)               # (B,n,c,H,hd)

    def chunk_fn(h, xs):
        ri, ki, vi, lwi = xs                            # (B,c,H,hd)...
        # decay of state from chunk start to just before step i: exp(lw_{i-1})
        lw_prev = jnp.pad(lwi[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0)))
        r_dec = ri * jnp.exp(lw_prev)                   # r'_i  (≤ 1, safe)
        # k'_j = k_j·exp(−lw_j) so r'_i·k'_j = exp(lw_{i−1} − lw_j)·r_i·k_j.
        # −lw_j grows with in-chunk position; clamp at 30 — the clamp only
        # bites when the true pair decay exp(lw_i−lw_j) is ≈ 0 anyway.
        k_dec = ki * jnp.exp(jnp.clip(-lwi, max=30.0))
        # intra-chunk: scores[i,j] = Σ_d r'_i k'_j  for j<i (strict lower-tri)
        scores = jnp.einsum("bihd,bjhd->bhij", r_dec, k_dec)
        tri = jnp.tril(jnp.ones((ri.shape[1], ri.shape[1]), bool), k=-1)
        scores = jnp.where(tri[None, None], scores, 0.0)
        y = jnp.einsum("bhij,bjhd->bihd", scores, vi)
        # current-token bonus: (r_i · (u∘k_i)) v_i
        bonus = jnp.einsum("bihd,hd,bihd->bih", ri, u, ki)
        y = y + bonus[..., None] * vi
        # inter-chunk: y_i += r'_i @ S_prev
        y = y + jnp.einsum("bihd,bhde->bihe", r_dec, h)
        # state update: S = diag(exp(lw_last)) S + Σ_j exp(lw_last - lw_j) k_j v_jᵀ
        lw_last = lwi[:, -1]                            # (B,H,hd)
        k_end = ki * jnp.exp(lw_last[:, None] - lwi)
        h_new = jnp.exp(lw_last)[..., None] * h + jnp.einsum(
            "bjhd,bjhe->bhde", k_end, vi)
        return h_new, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rs, ks_, vs, lw))
    h_last, ys = jax.lax.scan(chunk_fn, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, hd)
    return y.astype(r.dtype), h_last


def apply_time_mix(p: Dict, x: jnp.ndarray, cfg, state=None):
    """state: None or dict(shift:(B,1,d), h:(B,H,hd,hd)).  Returns (y, new_state)."""
    B, S, d = x.shape
    H, hd = cfg.n_heads, d // cfg.n_heads
    last = None if state is None else state["shift"]
    xprev = _shift(x, last)
    mu = p["mu"]
    xr, xk, xv, xw, xg = (x + (xprev - x) * mu[i] for i in range(5))
    r = (xr @ p["wr"]).reshape(B, S, H, hd)
    k = (xk @ p["wk"]).reshape(B, S, H, hd)
    v = (xv @ p["wv"]).reshape(B, S, H, hd)
    g = jax.nn.silu(xg @ p["wg"])
    # data-dependent decay (RWKV6): w = exp(-exp(w0 + lora(xw)))
    wlog = p["w0"] + (jnp.tanh(xw @ p["w_lora_a"]) @ p["w_lora_b"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(wlog)).reshape(B, S, H, hd)
    h0 = (jnp.zeros((B, H, hd, hd), jnp.float32) if state is None else state["h"])
    y, h_last = wkv_chunked(r, k, v, w, p["u"], h0, cfg.rwkv_chunk)
    y = y.reshape(B, S, d)
    y = apply_norm({"scale": p["ln"]}, y, "layernorm")  # group-norm-ish output norm
    y = (y * g) @ p["wo"]
    new_state = {"shift": x[:, -1:], "h": h_last}
    return y, new_state


def apply_channel_mix(p: Dict, x: jnp.ndarray, cfg, state=None):
    last = None if state is None else state["shift"]
    xprev = _shift(x, last)
    mu = p["mu"]
    xk = x + (xprev - x) * mu[0]
    xr = x + (xprev - x) * mu[1]
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    r = jax.nn.sigmoid(xr @ p["wr"])
    v = k @ p["wv"]
    return v * r, {"shift": x[:, -1:]}


def init_wkv_state(cfg, batch: int):
    d = cfg.d_model
    H, hd = cfg.n_heads, d // cfg.n_heads
    dt = dtype_of(cfg)
    return {
        "tm": {"shift": jnp.zeros((batch, 1, d), dt),
               "h": jnp.zeros((batch, H, hd, hd), jnp.float32)},
        "cm": {"shift": jnp.zeros((batch, 1, d), dt)},
    }
