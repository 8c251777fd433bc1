"""Decoder-only dense transformer LM.

Layers are scanned with stacked parameters: the program's size is O(1) in
depth.

Step functions:
  train_loss(params, batch)                 — next-token CE
  prefill(params, batch)                    — returns (last_logits, cache)
  decode_step(params, cache, token)         — one token against the cache
The KV cache holds (L,B,C,KV,hd) keys and values with their absolute
positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import attention as A
from repro.models.layers import basic as B

CACHE_PAD = 128  # decode caches get S + CACHE_PAD capacity


# ---------------------------------------------------------------------- blocks
def init_block(cfg, key):
    ks = jax.random.split(key, 4)
    return {"ln1": B.init_norm(cfg, ks[0]), "attn": A.init_attention(cfg, ks[1]),
            "ln2": B.init_norm(cfg, ks[2]), "mlp": B.init_mlp(cfg, ks[3])}


def _mix(cfg, p, x, attn_out):
    """Residual attn-out projection + MLP."""
    x = x + attn_out @ p["attn"]["wo"]
    h = B.apply_norm(p["ln2"], x, cfg.norm)
    return x + B.apply_mlp(p["mlp"], h, cfg)


def block_fwd(cfg, p, x, positions):
    B_, S, _ = x.shape
    h = B.apply_norm(p["ln1"], x, cfg.norm)
    q, k, v = A.qkv(p["attn"], h, cfg, positions)
    if S <= 512:
        o = A.full_attention(q, k, v)
    else:
        o = A.chunked_attention(q, k, v, cfg)
    o = o.reshape(B_, S, cfg.q_dim)
    return _mix(cfg, p, x, o), (k, v)


def _quantize_kv(t):
    """Per-(token, head) symmetric int8: (B,S,KV,hd) → (int8, bf16 scale)."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def block_decode(cfg, p, x, lcache, pos):
    """x: (B,1,d); lcache: dict(k,v,kv_pos[,k_scale,v_scale]) for this layer."""
    B_ = x.shape[0]
    h = B.apply_norm(p["ln1"], x, cfg.norm)
    q, k, v = A.qkv(p["attn"], h, cfg, jnp.full((1,), pos))
    if cfg.cache_quant and "k_scale" in lcache:
        kq, ks_new = _quantize_kv(k)
        vq, vs_new = _quantize_kv(v)
        kc, vc, kp = A.cache_update(lcache["k"], lcache["v"], lcache["kv_pos"],
                                    kq, vq, pos)
        ks, vs, _ = A.cache_update(lcache["k_scale"], lcache["v_scale"],
                                   lcache["kv_pos"], ks_new, vs_new, pos)
        # dequant fuses into the attention einsums on TPU: HBM reads stay int8
        kd = (kc.astype(jnp.bfloat16) * ks).astype(q.dtype)
        vd = (vc.astype(jnp.bfloat16) * vs).astype(q.dtype)
        o = A.decode_attention(q, kd, vd, kp, pos)
        new_cache = {"k": kc, "v": vc, "kv_pos": kp, "k_scale": ks, "v_scale": vs}
    else:
        kc, vc, kp = A.cache_update(lcache["k"], lcache["v"], lcache["kv_pos"],
                                    k, v, pos)
        o = A.decode_attention(q, kc, vc, kp, pos)
        new_cache = {"k": kc, "v": vc, "kv_pos": kp}
    o = o.reshape(B_, 1, cfg.q_dim)
    return _mix(cfg, p, x, o), new_cache


# -------------------------------------------------------------------- params
def init_lm(cfg, key):
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[2], cfg.n_layers)
    return {"embed": B.init_embedding(cfg, ks[0]),
            "final_norm": B.init_norm(cfg, ks[1]),
            "layers": jax.vmap(lambda k: init_block(cfg, k))(layer_keys)}


# --------------------------------------------------------------------- forward
def _backbone(cfg, params, x, positions, *, collect_kv: bool):
    """Returns (x, kv_stacks) — kv_stacks is None unless collect_kv."""
    def body(h, lp):
        out, kv = block_fwd(cfg, lp, h, positions)
        return out, (kv if collect_kv else None)

    body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
    return jax.lax.scan(body_fn, x, params["layers"])


def train_loss(cfg, params, batch):
    x = B.embed(params["embed"], batch["tokens"])
    x, _ = _backbone(cfg, params, x, jnp.arange(x.shape[1]), collect_kv=False)
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    return B.lm_loss_chunked(params["embed"], x, batch["tokens"],
                             chunk=cfg.loss_chunk)


# ---------------------------------------------------------------------- caches
def _full_cache_from_kv(k, v, S, pad=CACHE_PAD):
    """k,v: (B,S,KV,hd) → capacity S+pad cache."""
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kv_pos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                              jnp.full((pad,), -1, jnp.int32)])
    return {"k": kc, "v": vc, "kv_pos": kv_pos}


def prefill(cfg, params, batch):
    x = B.embed(params["embed"], batch["tokens"])
    S = x.shape[1]
    x, (k, v) = _backbone(cfg, params, x, jnp.arange(S), collect_kv=True)
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    logits = B.unembed(params["embed"], x[:, -1:])
    return logits, {"pos": jnp.int32(S),
                    "full": jax.vmap(lambda kk, vv: _full_cache_from_kv(kk, vv, S))(k, v)}


def init_cache(cfg, batch_size: int, seq_len: int):
    """Empty cache with capacity for seq_len history (+pad)."""
    dt = B.dtype_of(cfg)
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    L, C = cfg.n_layers, seq_len + CACHE_PAD
    kv_dt = jnp.int8 if cfg.cache_quant else dt
    full = {"k": jnp.zeros((L, batch_size, C, KV, hd), kv_dt),
            "v": jnp.zeros((L, batch_size, C, KV, hd), kv_dt),
            "kv_pos": jnp.full((L, C), -1, jnp.int32)}
    if cfg.cache_quant:
        full["k_scale"] = jnp.zeros((L, batch_size, C, KV, 1), jnp.bfloat16)
        full["v_scale"] = jnp.zeros((L, batch_size, C, KV, 1), jnp.bfloat16)
    return {"pos": jnp.int32(seq_len), "full": full}


def decode_step(cfg, params, cache, token):
    """token: (B,1) int32 → (logits (B,1,V), new cache)."""
    pos = cache["pos"]
    x = B.embed(params["embed"], token)

    def body(h, xs):
        lp, lc = xs
        return block_decode(cfg, lp, h, lc, pos)

    x, ncache = jax.lax.scan(body, x, (params["layers"], cache["full"]))
    new_cache = {"pos": pos + 1, "full": ncache}
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    logits = B.unembed(params["embed"], x)
    return logits, new_cache
