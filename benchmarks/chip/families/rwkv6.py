"""RWKV-6 "Finch": attention-free blocks of time mix (a per-head linear
recurrence with data-dependent decay) and channel mix, each fed a token
shift.

Reference: the recurrence stepped token by token in float32, as the paper
writes it, per head with S in R^{hd x hd}:
    y_t = r_t . (S_{t-1} + (u * k_t) v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T,   w_t = exp(-exp(w0 + lora(x_w)))
The configuration's departures from the paper (static token-shift mixes, one
LayerNorm over the WKV output) are followed, as its file states.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from families.common import layer_norm, matmul, take_layer


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """r, k, v, w: (B, S, H, hd); u: (H, hd) -> y (B, S, H, hd)."""
    B, S, H, hd = r.shape

    def step(state, xs):
        rt, kt, vt, wt = xs                                   # (B, H, hd)
        kv = kt[..., :, None] * vt[..., None, :]              # (B, H, hd, hd)
        y = jnp.einsum("bhd,bhde->bhe", rt, state + u[None, :, :, None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return wt[..., :, None] * state + kv, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((B, H, hd, hd), jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, stacked, i, m, quant):
    m = dict(m)
    p = take_layer(stacked, i)
    B, S, d = x.shape
    H, hd = m["n_heads"], m["head_dim"]
    tm, cm = p["tm"], p["cm"]
    h = layer_norm(x, p["ln1"]["scale"])
    hp = _shift(h)
    xr, xk, xv, xw, xg = (h + (hp - h) * tm["mu"][j] for j in range(5))
    r = matmul(xr, tm["wr"], quant).reshape(B, S, H, hd)
    k = matmul(xk, tm["wk"], quant).reshape(B, S, H, hd)
    v = matmul(xv, tm["wv"], quant).reshape(B, S, H, hd)
    g = jax.nn.silu(matmul(xg, tm["wg"], quant))
    wlog = tm["w0"] + matmul(jnp.tanh(matmul(xw, tm["w_lora_a"], quant)),
                             tm["w_lora_b"], quant)
    w = jnp.exp(-jnp.exp(wlog)).reshape(B, S, H, hd)
    y = _wkv(r, k, v, w, tm["u"]).reshape(B, S, d)
    y = layer_norm(y, tm["ln"])
    x = x + matmul(y * g, tm["wo"], quant)
    h = layer_norm(x, p["ln2"]["scale"])
    hp = _shift(h)
    xk, xr = h + (hp - h) * cm["mu"][0], h + (hp - h) * cm["mu"][1]
    kk = jnp.square(jax.nn.relu(matmul(xk, cm["wk"], quant)))
    rr = jax.nn.sigmoid(matmul(xr, cm["wr"], quant))
    return x + matmul(kk, cm["wv"], quant) * rr


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, embed, final_norm, m, quant):
    m = dict(m)
    x = layer_norm(x, final_norm["scale"])
    w = embed["unembed"] if "unembed" in embed else embed["table"].T
    return matmul(x, w, quant)


def logits(params, tokens, m: Dict, quant: str | None = None) -> jax.Array:
    """Float32 logits (B, S, V) at every position of ``tokens`` (B, S)."""
    key = tuple(sorted(m.items()))
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x = layer_norm(x, params["ln_in"]["scale"])
    for i in range(m["n_layers"]):
        x = _layer(x, params["layers"], i, key, quant)
    return _head(x, params["embed"], params["final_norm"], key, quant)


def param_count(m: Dict) -> int:
    d, f, L, r = m["d_model"], m["d_ff"], m["n_layers"], m["decay_lora"]
    per = (6 * d * d + 2 * d * f + 2 * d * r   # wr wk wv wg wo, cm wr wk wv
           + 5 * d + d + d + d                  # tm mu, w0, u, ln
           + 2 * d + 2 * d)                     # cm mu, ln1, ln2
    emb = m["vocab_size"] * d * (1 if m["tie_embeddings"] else 2)
    return emb + L * per + 2 * d                # + ln_in, final_norm


def state_bytes(m: Dict, batch: int) -> int:
    """Decode state: the float32 WKV state and two bfloat16 token shifts."""
    L, d, H, hd = m["n_layers"], m["d_model"], m["n_heads"], m["head_dim"]
    return L * batch * (H * hd * hd * 4 + 2 * d * 2)


def decode_cost(m: Dict, batch: int, ctx: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of one decode step: every weight read once (w0 and
    u are float32), the state read and written.  Matmul FLOPs plus the
    recurrence's 5 hd^2 per head; ``ctx`` does not matter."""
    d, f, L, V = m["d_model"], m["d_ff"], m["n_layers"], m["vocab_size"]
    H, hd, r = m["n_heads"], m["head_dim"], m["decay_lora"]
    per_tok = L * (6 * d * d + 2 * d * f + 2 * d * r) + V * d
    flops = batch * (2 * per_tok + L * 5 * H * hd * hd)
    wbytes = 2 if m["dtype"] == "bfloat16" else 4
    f32_leaves = m["n_layers"] * 2 * d           # w0 and u
    weights = (param_count(m) - f32_leaves) * wbytes + f32_leaves * 4
    return float(flops), float(weights + 2 * state_bytes(m, batch))
