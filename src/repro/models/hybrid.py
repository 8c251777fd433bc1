"""Zamba2 hybrid: a stack of Mamba2 layers, some of which ("hybrid" layers,
``cfg.hybrid_layer_ids``) first call a weight-shared attention+MLP block.

With x0 the token embeddings and h = x0, layer i computes
    hybrid i (call j, block b = j % n_mem_blocks):
        u = RMSNorm([h ; x0])                  2d channels
        a = attention_b(u)                     q/k/v 2d -> H x hd, rope, out -> d
        t = RMSNorm(a)
        m = mlp_b(t, adapter_j)                gated, [g ; p] = t W1_b + t A_j B_j
        tau = m W_lin_j
        h <- h + Mamba2_i(RMSNorm(h + tau))
    otherwise:
        h <- h + Mamba2_i(RMSNorm(h))
and the logits are RMSNorm(h) against the tied embedding.  The shared block
has no residual of its own: its output enters only the Mamba2 input.

Every layer, shared block and call keeps its own parameter leaves, and the
layers run as an unrolled loop: a dot then reads its weight where it lies,
where a slice of a stacked (layers, ...) weight is copied first on the TPU.
The decode cache holds the two kinds of state side by side, one entry per
layer or call: each layer's Mamba2 state (conv window and float32 SSM state,
rewritten each step) under ``mamba``, and each call's append-only K/V under
``attn``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import attention as A
from repro.models.layers import basic as B
from repro.models.layers import ssm as S
from repro.models.transformer import CACHE_PAD, _full_cache_from_kv


def _calls(cfg):
    """{hybrid layer: its call rank}."""
    return {i: j for j, i in enumerate(cfg.hybrid_ids)}


def _attn_scale(cfg) -> float:
    # Zamba2 scales scores by (head_dim / 2)^-1/2: its queries come from the
    # 2d-wide concatenated input
    return (cfg.head_dim / 2) ** -0.5


# ------------------------------------------------------------------- params
def _init_mamba_layer(cfg, key):
    k1, k2 = jax.random.split(key)
    return {"ln": B.init_norm(cfg, k1), "ssm": S.init_ssm(cfg, k2)}


def _init_shared(cfg, key):
    ks = jax.random.split(key, 3)
    return {"ln1": {"scale": jnp.ones((2 * cfg.d_model,), B.dtype_of(cfg))},
            "attn": A.init_attention(cfg, ks[0], d_in=2 * cfg.d_model),
            "ln2": B.init_norm(cfg, ks[1]),
            "mlp": B.init_mlp(cfg, ks[2])}


def _init_call(cfg, key):
    dt, d, r = B.dtype_of(cfg), cfg.d_model, cfg.adapter_rank
    ks = jax.random.split(key, 3)
    p = {"linear": B.dense_init(ks[0], (d, d), dt)}
    if r:
        p["adapter"] = {"a": B.dense_init(ks[1], (d, r), dt),
                        "b": B.dense_init(ks[2], (r, 2 * cfg.d_ff), dt)}
    return p


def init_lm(cfg, key):
    ks = jax.random.split(key, 5)
    each = lambda f, k, n: [f(cfg, kk) for kk in jax.random.split(k, n)]
    p = {"embed": B.init_embedding(cfg, ks[0]),
         "mamba": each(_init_mamba_layer, ks[1], cfg.n_layers),
         "shared": each(_init_shared, ks[2], cfg.n_mem_blocks),
         "final_norm": B.init_norm(cfg, ks[3])}
    if cfg.hybrid_ids:
        p["calls"] = each(_init_call, ks[4], len(cfg.hybrid_ids))
    return p


# ------------------------------------------------------------------- blocks
def _mamba(cfg, lp, h, tau=None, state=None, decode=False):
    """One Mamba2 layer: h + Mamba2(RMSNorm(h [+ tau]))."""
    with jax.named_scope("mamba2"):
        u = B.apply_norm(lp["ln"], h if tau is None else h + tau, cfg.norm)
        if decode:
            y, st = S.decode_ssm(lp["ssm"], u, cfg, state)
        else:
            y, st = S.apply_ssm(lp["ssm"], u, cfg, state)
    return h + y, st


def _mlp_out(cfg, sp, cp, a):
    """tau from the attention output a: norm, adapted gated MLP, the call's
    own projection."""
    t = B.apply_norm(sp["ln2"], a, cfg.norm)
    mp = sp["mlp"]
    g, p = t @ mp["wg"], t @ mp["wi"]
    if "adapter" in cp:
        low = (t @ cp["adapter"]["a"]) @ cp["adapter"]["b"]
        g, p = g + low[..., : cfg.d_ff], p + low[..., cfg.d_ff :]
    m = (B.ACTIVATIONS[cfg.act](g) * p) @ mp["wo"]
    return m @ cp["linear"]


def _shared_fwd(cfg, params, j, h, x0, positions):
    sp, cp = params["shared"][j % cfg.n_mem_blocks], params["calls"][j]
    with jax.named_scope("shared_block"):
        u = B.apply_norm(sp["ln1"], jnp.concatenate([h, x0], -1), cfg.norm)
        q, k, v = A.qkv(sp["attn"], u, cfg, positions)
        if h.shape[1] <= 512:
            o = A.full_attention(q, k, v, scale=_attn_scale(cfg))
        else:
            o = A.chunked_attention(q, k, v, cfg, scale=_attn_scale(cfg))
        a = o.reshape(h.shape[0], h.shape[1], cfg.q_dim) @ sp["attn"]["wo"]
        return _mlp_out(cfg, sp, cp, a), (k, v)


def _shared_decode(cfg, params, j, h, x0, kv, pos):
    sp, cp = params["shared"][j % cfg.n_mem_blocks], params["calls"][j]
    with jax.named_scope("shared_block"):
        u = B.apply_norm(sp["ln1"], jnp.concatenate([h, x0], -1), cfg.norm)
        # head_dim 224 is no multiple of the TPU's 128 lanes: left free to
        # fuse the split into heads into the dot, the compiler copies each
        # 2d-row weight to a transposed layout every step; the barrier
        # keeps the dots flat, so only the (B, 1, H*hd) outputs are moved
        at, positions = sp["attn"], jnp.full((1,), pos)
        q, k, v = (t.reshape(h.shape[0], 1, -1, cfg.head_dim)
                   for t in jax.lax.optimization_barrier(
                       (u @ at["wq"], u @ at["wk"], u @ at["wv"])))
        q = B.apply_rope(q, positions, cfg.rope_theta)
        k = B.apply_rope(k, positions, cfg.rope_theta)
        kc, vc, kp = A.cache_update(kv["k"], kv["v"], kv["kv_pos"], k, v, pos)
        o = A.decode_attention(q, kc, vc, kp, pos, scale=_attn_scale(cfg))
        a = o.reshape(h.shape[0], 1, cfg.q_dim) @ sp["attn"]["wo"]
        return _mlp_out(cfg, sp, cp, a), {"k": kc, "v": vc, "kv_pos": kp}


# ------------------------------------------------------------------ forward
def _forward(cfg, params, x0, positions):
    """Returns (h, [Mamba2 state per layer], [(k, v) per call])."""
    mamba = functools.partial(_mamba, cfg)
    if cfg.remat == "full":
        mamba = jax.checkpoint(mamba)
    calls = _calls(cfg)
    h, states, kvs = x0, [], []
    for i, lp in enumerate(params["mamba"]):
        tau = None
        if i in calls:
            tau, kv = _shared_fwd(cfg, params, calls[i], h, x0, positions)
            kvs.append(kv)
        h, st = mamba(lp, h, tau)
        states.append(st)
    return h, states, kvs


def train_loss(cfg, params, batch):
    x = B.embed(params["embed"], batch["tokens"])
    x, _, _ = _forward(cfg, params, x, jnp.arange(x.shape[1]))
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    return B.lm_loss_chunked(params["embed"], x, batch["tokens"],
                             chunk=cfg.loss_chunk)


def prefill(cfg, params, batch):
    x = B.embed(params["embed"], batch["tokens"])
    S_ = x.shape[1]
    x, states, kvs = _forward(cfg, params, x, jnp.arange(S_))
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    logits = B.unembed(params["embed"], x[:, -1:])
    cache = {"pos": jnp.int32(S_), "mamba": states}
    if kvs:
        cache["attn"] = [_full_cache_from_kv(k, v, S_) for k, v in kvs]
    return logits, cache


def init_cache(cfg, batch_size: int, seq_len: int):
    dt = B.dtype_of(cfg)
    C = seq_len + CACHE_PAD
    kv_shape = (batch_size, C, cfg.n_kv_heads, cfg.head_dim)
    cache = {"pos": jnp.int32(seq_len),
             "mamba": [S.init_ssm_state(cfg, batch_size)
                       for _ in range(cfg.n_layers)]}
    if cfg.hybrid_ids:
        cache["attn"] = [{"k": jnp.zeros(kv_shape, dt),
                          "v": jnp.zeros(kv_shape, dt),
                          "kv_pos": jnp.full((C,), -1, jnp.int32)}
                         for _ in cfg.hybrid_ids]
    return cache


def decode_step(cfg, params, cache, token):
    pos = cache["pos"]
    x0 = B.embed(params["embed"], token)
    calls = _calls(cfg)
    h, states, kvs = x0, [], []
    for i, (lp, st) in enumerate(zip(params["mamba"], cache["mamba"])):
        tau = None
        if i in calls:
            j = calls[i]
            tau, kv = _shared_decode(cfg, params, j, h, x0, cache["attn"][j],
                                     pos)
            kvs.append(kv)
        h, st = _mamba(cfg, lp, h, tau, st, decode=True)
        states.append(st)
    x = B.apply_norm(params["final_norm"], h, cfg.norm)
    logits = B.unembed(params["embed"], x)
    new_cache = {"pos": pos + 1, "mamba": states}
    if kvs:
        new_cache["attn"] = kvs
    return logits, new_cache
