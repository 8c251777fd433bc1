"""Mamba2 layer via the chunked SSD (state-space dual) form.

TPU adaptation: instead of the sequential per-token recurrence (GPU-style
selective scan), the sequence is split into chunks; within a chunk the SSD
identity turns the recurrence into masked matmuls (MXU work), and a short
``lax.scan`` carries the (nh, hp, ds) state across chunks.  Decode is the
single-token recurrence.

Recurrence (scalar-identity A per head; head n reads B/C group g(n) =
n // (nh / n_groups)):
    h_t = exp(dt_t·A) · h_{t-1} + dt_t · x_t ⊗ B_t[g]     y_t = h_t·C_t[g] + D·x_t
The output is y gated by silu(z) and RMS-normalised over each group's
channels (Mamba2's gated RMSNorm), then projected back to d_model.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.basic import NORM_EPS, dense_init, dtype_of


def _conv_dim(cfg) -> int:
    return cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state


def init_ssm(cfg, key):
    dt = dtype_of(cfg)
    d, di, nh = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    conv_dim = _conv_dim(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "in_proj": dense_init(ks[0], (d, di + conv_dim + nh), dt),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, conv_dim), dt, scale=0.5),
        "A_log": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_proj": dense_init(ks[2], (di, d), dt),
        "gate_norm": jnp.ones((di,), dt),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((conv_dim,), dt)
    return p


def _split_proj(cfg, proj):
    di = cfg.d_inner
    conv_dim = _conv_dim(cfg)
    z = proj[..., :di]
    xBC = proj[..., di : di + conv_dim]
    dt = proj[..., di + conv_dim :]
    return z, xBC, dt


def _split_xBC(cfg, xBC):
    """(..., conv_dim) -> x (..., nh, hp), B and C (..., G, ds)."""
    di, G, ds = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    lead = xBC.shape[:-1]
    xs = xBC[..., :di].reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim))
    B_in = xBC[..., di : di + G * ds].reshape(lead + (G, ds))
    C_in = xBC[..., di + G * ds :].reshape(lead + (G, ds))
    return xs, B_in, C_in


def _causal_conv(xBC, conv_w, conv_b=None, conv_state=None):
    """Depthwise causal conv over time (+ bias), then silu.  xBC: (B,S,Cd);
    conv_w: (K,Cd).  conv_state: (B,K-1,Cd) carried inputs for decode."""
    K = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros_like(xBC[:, : K - 1])
    else:
        pad = conv_state
    xp = jnp.concatenate([pad, xBC], axis=1)
    out = sum(xp[:, i : i + xBC.shape[1]] * conv_w[i] for i in range(K))
    if conv_b is not None:
        out = out + conv_b
    new_state = xp[:, -(K - 1) :]
    return jax.nn.silu(out), new_state


def gated_rms_norm(y, z, weight, groups: int):
    """Mamba2's output norm: y·silu(z), RMS-normalised over each of
    ``groups`` equal slices of the channels, times ``weight``; float32
    inside, returned in y's dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    gs = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    gs = gs * jax.lax.rsqrt((gs * gs).mean(-1, keepdims=True) + NORM_EPS)
    return (gs.reshape(g.shape) * weight.astype(jnp.float32)).astype(y.dtype)


def _segsum_decay(dA):
    """dA: (B,c,nh) per-step log-decay → L[i,j]=exp(Σ_{t=j+1..i} dA_t) lower-tri."""
    cum = jnp.cumsum(dA, axis=1)                       # (B,c,nh)
    diff = cum[:, :, None, :] - cum[:, None, :, :]     # (B,i,j,nh)
    c = dA.shape[1]
    tri = jnp.tril(jnp.ones((c, c), bool))
    return jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0), cum


def ssm_chunked(cfg, x, B_in, C_in, dt, A, h0=None):
    """Chunked SSD.  x:(B,S,nh,hp)  B_in/C_in:(B,S,G,ds)  dt:(B,S,nh)
    post-softplus, A:(nh,) negative.  Returns y:(B,S,nh,hp),
    h_last:(B,nh,hp,ds)."""
    Bsz, S, nh, hp = x.shape
    G, ds = B_in.shape[-2:]
    hg = nh // G                                       # heads per group
    c = min(cfg.ssm_chunk, S)
    while S % c:
        c //= 2
    n = S // c
    xc = x.reshape(Bsz, n, c, G, hg, hp).astype(jnp.float32)
    Bc = B_in.reshape(Bsz, n, c, G, ds).astype(jnp.float32)
    Cc = C_in.reshape(Bsz, n, c, G, ds).astype(jnp.float32)
    dtc = dt.reshape(Bsz, n, c, nh).astype(jnp.float32)
    dAc = dtc * A[None, None, None, :]                 # log-decay per step

    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, hp, ds), jnp.float32)
    h0 = h0.reshape(Bsz, G, hg, hp, ds)

    def chunk(h, xs):
        # (B,c,G,hg,hp), (B,c,G,ds), (B,c,G,ds), (B,c,nh), (B,c,nh)
        xj, Bj, Cj, dAj, dtj = xs
        L, cum = _segsum_decay(dAj)                    # (B,i,j,nh), (B,c,nh)
        L = L.reshape(L.shape[:3] + (G, hg))
        cum = cum.reshape(Bsz, c, G, hg)
        xdt = xj * dtj.reshape(Bsz, c, G, hg)[..., None]  # dt-weighted inputs
        scores = jnp.einsum("bigs,bjgs->bgij", Cj, Bj)
        y_intra = jnp.einsum("bgij,bijgh,bjghp->bighp", scores, L, xdt)
        y_inter = jnp.einsum("bigs,bghps->bighp", Cj, h) * jnp.exp(cum)[..., None]
        decay_to_end = jnp.exp(cum[:, -1:] - cum)      # (B,c,G,hg)
        h_new = jnp.exp(cum[:, -1])[..., None, None] * h + jnp.einsum(
            "bjgs,bjghp->bghps", Bj, xdt * decay_to_end[..., None])
        return h_new, y_intra + y_inter

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (xc, Bc, Cc, dAc, dtc))
    h_last, ys = jax.lax.scan(chunk, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, nh, hp)
    return y.astype(x.dtype), h_last.reshape(Bsz, nh, hp, ds)


def apply_ssm(params: Dict, x: jnp.ndarray, cfg, state=None):
    """Full Mamba2 mixer over a sequence.
    state: None (train/prefill from scratch) or dict(conv, h) for resume.
    Returns (y, new_state)."""
    B, S, d = x.shape
    proj = x @ params["in_proj"]
    z, xBC, dt_raw = _split_proj(cfg, proj)
    conv_state = None if state is None else state["conv"]
    xBC, new_conv = _causal_conv(xBC, params["conv_w"], params.get("conv_b"),
                                 conv_state)
    xs, B_in, C_in = _split_xBC(cfg, xBC)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])                      # (nh,) negative
    h0 = None if state is None else state["h"]
    y, h_last = ssm_chunked(cfg, xs, B_in, C_in, dt, A, h0=h0)
    y = y + xs * params["D"][None, None, :, None].astype(xs.dtype)
    y = gated_rms_norm(y.reshape(B, S, cfg.d_inner), z, params["gate_norm"],
                       cfg.ssm_groups)
    out = y @ params["out_proj"]
    return out, {"conv": new_conv, "h": h_last}


def decode_ssm(params: Dict, x: jnp.ndarray, cfg, state):
    """Single-token recurrence.  x: (B,1,d)."""
    B, _, d = x.shape
    G, nh = cfg.ssm_groups, cfg.ssm_heads
    hg = nh // G
    proj = x @ params["in_proj"]
    z, xBC, dt_raw = _split_proj(cfg, proj)
    xBC, new_conv = _causal_conv(xBC, params["conv_w"], params.get("conv_b"),
                                 state["conv"])
    xs, B_in, C_in = _split_xBC(cfg, xBC[:, 0])    # (B,nh,hp), (B,G,ds) x2
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B,nh)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * A)                                    # (B,nh)
    xg = xs.astype(jnp.float32).reshape(B, G, hg, -1)
    h = state["h"].reshape(B, G, hg, cfg.ssm_head_dim, -1)
    h = h * decay.reshape(B, G, hg)[..., None, None] + jnp.einsum(
        "bgs,bghp,bgh->bghps", B_in.astype(jnp.float32), xg,
        dt.reshape(B, G, hg))
    y = jnp.einsum("bgs,bghps->bghp", C_in.astype(jnp.float32), h)
    y = y.reshape(B, nh, -1) + xs.astype(jnp.float32) * params["D"][None, :, None]
    y = gated_rms_norm(y.reshape(B, 1, cfg.d_inner).astype(x.dtype), z,
                       params["gate_norm"], G)
    return y @ params["out_proj"], {"conv": new_conv,
                                    "h": h.reshape(state["h"].shape)}


def init_ssm_state(cfg, batch: int):
    nh, hp, ds = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, _conv_dim(cfg)),
                          jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32),
        "h": jnp.zeros((batch, nh, hp, ds), jnp.float32),
    }
