"""Zamba2 hybrid: Mamba2 layers, some of which ("hybrid" layers) first call
one of a few weight-shared attention+MLP blocks, in turn, over the
concatenation of the hidden state and the token embeddings.

Reference, from the equations (x0 = embedding(tokens), h = x0; hybrid layer
i is call j of block b = j mod n_mem_blocks):
    u = RMSNorm_2d([h ; x0])
    a = softmax(q k^T (hd/2)^-1/2 + causal) v W_o     q, k, v = u W_{q,k,v}, rope
    t = RMSNorm_d(a)
    [g ; p] = t W_1 + (t A_j) B_j,   m = (gelu(g) * p) W_2,   tau = m W_lin_j
    h <- h + Mamba2_i(RMSNorm_d(h + tau))            (plain layers: tau = 0)
    logits = RMSNorm_d(h) E^T
Mamba2: [z ; xBC ; dt] = x W_in; xBC = silu(causal depthwise conv(xBC) + b);
x, B, C = split(xBC), head n reading B/C group n // (heads / groups);
Delta = softplus(dt + dt_bias), A = -exp(A_log);
H_t = exp(Delta_t A) H_{t-1} + Delta_t x_t (x) B_t,  y_t = H_t C_t + D x_t,
stepped token by token; y = GroupRMSNorm(y * silu(z)) w; out = y W_out.
GELU is the erf form.  Attention is plain softmax over the whole sequence.
The norms' epsilon is the program's (families/common.py NORM_EPS).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from families.common import NORM_EPS, matmul

HI = jax.lax.Precision.HIGHEST


def _check(m: Dict) -> None:
    """The reference computes only what it implements."""
    if (m["norm"] != "rmsnorm" or m["mlp_kind"] != "swiglu"
            or m["act"] != "gelu_exact" or not m["tie_embeddings"]):
        raise ValueError(f"the zamba2 reference does not implement {m}")


def _key(m: Dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def _rms(x, w):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + NORM_EPS) * w


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / jnp.sqrt(2.0)))


def _rope(x, theta: float):
    """x: (B, S, H, hd), rotated at positions 0..S-1 (the halves pair)."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dims(m: Dict):
    d = m["d_model"]
    di = m["ssm_expand"] * d
    nh = di // m["ssm_head_dim"]
    conv = di + 2 * m["ssm_groups"] * m["ssm_state"]
    return d, di, nh, conv


def _hybrid(m: Dict):
    return [i for i in m["hybrid_layer_ids"] if i < m["n_layers"]]


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _mamba(h, tau, layer, m, quant):
    m = dict(m)
    p = _f32(layer)
    s = p["ssm"]
    Bsz, S, _ = h.shape
    d, di, nh, conv = _dims(m)
    G, N, hp, K = m["ssm_groups"], m["ssm_state"], m["ssm_head_dim"], \
        m["ssm_conv"]
    u = _rms(h if tau is None else h + tau, p["ln"]["scale"])
    proj = matmul(u, s["in_proj"], quant)
    z, xBC, dt = proj[..., :di], proj[..., di:di + conv], proj[..., di + conv:]
    xp = jnp.concatenate([jnp.zeros((Bsz, K - 1, conv)), xBC], axis=1)
    xBC = sum(xp[:, k:k + S] * s["conv_w"][k] for k in range(K))
    if m["ssm_conv_bias"]:
        xBC = xBC + s["conv_b"]
    xBC = jax.nn.silu(xBC)
    x = xBC[..., :di].reshape(Bsz, S, nh, hp)
    Bg = xBC[..., di:di + G * N].reshape(Bsz, S, G, N)
    Cg = xBC[..., di + G * N:].reshape(Bsz, S, G, N)
    Bh = jnp.repeat(Bg, nh // G, axis=2)              # (B, S, nh, N)
    Ch = jnp.repeat(Cg, nh // G, axis=2)
    delta = jax.nn.softplus(dt + s["dt_bias"])         # (B, S, nh)
    A = -jnp.exp(s["A_log"])

    def step(H, xs):
        xt, bt, ct, dlt = xs
        H = jnp.exp(dlt * A)[..., None, None] * H \
            + (dlt[..., None] * xt)[..., :, None] * bt[..., None, :]
        return H, jnp.einsum("bhpn,bhn->bhp", H, ct, precision=HI)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bh, Ch, delta))
    _, ys = jax.lax.scan(step, jnp.zeros((Bsz, nh, hp, N)), xs)
    y = jnp.moveaxis(ys, 0, 1) + s["D"][:, None] * x
    y = (y.reshape(Bsz, S, di) * jax.nn.silu(z)).reshape(Bsz, S, G, di // G)
    y = _rms(y, 1.0).reshape(Bsz, S, di) * s["gate_norm"]
    return h + matmul(y, s["out_proj"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _shared(h, x0, block, call, m, quant):
    m = dict(m)
    sp, cp = _f32(block), _f32(call)
    Bsz, S, _ = h.shape
    H, KV, hd, f = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    u = _rms(jnp.concatenate([h, x0], -1), sp["ln1"]["scale"])
    at = sp["attn"]
    q = _rope(matmul(u, at["wq"], quant).reshape(Bsz, S, H, hd), m["rope_theta"])
    k = _rope(matmul(u, at["wk"], quant).reshape(Bsz, S, KV, hd), m["rope_theta"])
    v = matmul(u, at["wv"], quant).reshape(Bsz, S, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * (hd / 2) ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(Bsz, S, H * hd)
    t = _rms(matmul(o, at["wo"], quant), sp["ln2"]["scale"])
    mlp = sp["mlp"]
    gp = matmul(t, jnp.concatenate([mlp["wg"], mlp["wi"]], -1), quant)
    if "adapter" in cp:
        gp = gp + matmul(matmul(t, cp["adapter"]["a"], quant),
                         cp["adapter"]["b"], quant)
    mm = matmul(_gelu(gp[..., :f]) * gp[..., f:], mlp["wo"], quant)
    return matmul(mm, cp["linear"], quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, embed, final_norm, quant):
    return matmul(_rms(h, final_norm["scale"].astype(jnp.float32)),
                  embed["table"].T, quant)


def logits(params, tokens, m: Dict, quant: str | None = None) -> jax.Array:
    """Float32 logits (B, S, V) at every position of ``tokens`` (B, S)."""
    _check(m)
    key = _key(m)
    hyb = _hybrid(m)
    x0 = params["embed"]["table"][tokens].astype(jnp.float32)
    h = x0
    for i in range(m["n_layers"]):
        tau = None
        if i in hyb:
            j = hyb.index(i)
            tau = _shared(h, x0, params["shared"][j % m["n_mem_blocks"]],
                          params["calls"][j], key, quant)
        h = _mamba(h, tau, params["mamba"][i], key, quant)
    return _head(h, params["embed"], params["final_norm"], quant)


def param_count(m: Dict) -> int:
    """Every weight: the Mamba2 layers (in and out projections, conv and its
    bias, A_log, D, dt_bias, the gated norm and the layer's norm), the shared
    blocks (q/k/v from 2d, o, the gated MLP, two norms), per call the
    projection and the adapter, the tied embedding and the final norm."""
    _check(m)
    d, di, nh, conv = _dims(m)
    f, r, K = m["d_ff"], m["adapter_rank"], m["ssm_conv"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    mamba = (d * (di + conv + nh) + K * conv + conv * m["ssm_conv_bias"]
             + 3 * nh + di + di * d + d)
    shared = 2 * d * (q + 2 * kv) + q * d + 3 * d * f + 2 * d + d
    call = d * d + r * (d + 2 * f)
    return (m["vocab_size"] * d + d + m["n_layers"] * mamba
            + m["n_mem_blocks"] * shared + len(_hybrid(m)) * call)


def state_bytes(m: Dict, batch: int) -> int:
    """The decode state each step rewrites: per layer the float32 SSM state
    (heads x head_dim x d_state) and the conv window (d_conv - 1 inputs)."""
    d, di, nh, conv = _dims(m)
    wbytes = 2 if m["dtype"] == "bfloat16" else 4
    return m["n_layers"] * batch * (nh * m["ssm_head_dim"] * m["ssm_state"] * 4
                                    + (m["ssm_conv"] - 1) * conv * wbytes)


def decode_cost(m: Dict, batch: int, ctx: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step needs at ``ctx`` valid cache
    positions (the new token's included): every weight read once (A_log, D
    and dt_bias are float32), the Mamba2 state read and written, each call's
    valid keys and values read and the new ones written.  Matmul FLOPs plus
    the recurrence's 5 head_dim x d_state per head."""
    d, di, nh, conv = _dims(m)
    f, r, L, V = m["d_ff"], m["adapter_rank"], m["n_layers"], m["vocab_size"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    calls = len(_hybrid(m))
    per_tok = (L * (d * (di + conv + nh) + di * d)
               + calls * (2 * d * (q + 2 * kv) + q * d + 3 * d * f
                          + d * d + r * (d + 2 * f))
               + V * d)
    flops = batch * (2 * per_tok + calls * 4 * q * ctx
                     + L * 5 * nh * m["ssm_head_dim"] * m["ssm_state"])
    wbytes = 2 if m["dtype"] == "bfloat16" else 4
    f32_leaves = L * 3 * nh
    weights = (param_count(m) - f32_leaves) * wbytes + f32_leaves * 4
    cache = calls * 2 * batch * kv * wbytes * ctx  # ctx - 1 read, 1 written
    return float(flops), float(weights + 2 * state_bytes(m, batch) + cache)
