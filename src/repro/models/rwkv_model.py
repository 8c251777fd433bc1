"""RWKV6 language model: attention-free; each block = time-mix + channel-mix
with token-shift.  Decode carries (shift, wkv-state) per layer — O(1) memory in
context length."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import basic as B
from repro.models.layers import rwkv as R


def init_lm(cfg, key):
    ks = jax.random.split(key, 3)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)

    def init_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        p = R.init_rwkv_block(cfg, k1)
        return {"ln1": B.init_norm(cfg, k2), "ln2": B.init_norm(cfg, k3), **p}

    return {
        "embed": B.init_embedding(cfg, ks[1]),
        "ln_in": B.init_norm(cfg, ks[2]),
        "layers": jax.vmap(init_layer)(layer_keys),
        "final_norm": B.init_norm(cfg, jax.random.fold_in(key, 5)),
    }


def _block(cfg, lp, x, state=None):
    tm_state = None if state is None else state["tm"]
    cm_state = None if state is None else state["cm"]
    h = B.apply_norm(lp["ln1"], x, cfg.norm)
    y, new_tm = R.apply_time_mix(lp["tm"], h, cfg, tm_state)
    x = x + y
    h = B.apply_norm(lp["ln2"], x, cfg.norm)
    y, new_cm = R.apply_channel_mix(lp["cm"], h, cfg, cm_state)
    x = x + y
    return x, {"tm": new_tm, "cm": new_cm}


def _forward(cfg, params, x, collect: bool):
    remat = cfg.remat == "full"

    def body(h, lp):
        h, st = _block(cfg, lp, h)
        return h, (st if collect else None)

    body_fn = jax.checkpoint(body) if remat else body
    return jax.lax.scan(body_fn, x, params["layers"])


def train_loss(cfg, params, batch):
    x = B.embed(params["embed"], batch["tokens"])
    x = B.apply_norm(params["ln_in"], x, cfg.norm)
    x, _ = _forward(cfg, params, x, collect=False)
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    return B.lm_loss_chunked(params["embed"], x, batch["tokens"],
                             chunk=cfg.loss_chunk)


def prefill(cfg, params, batch):
    x = B.embed(params["embed"], batch["tokens"])
    x = B.apply_norm(params["ln_in"], x, cfg.norm)
    x, states = _forward(cfg, params, x, collect=True)
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    logits = B.unembed(params["embed"], x[:, -1:])
    return logits, {"pos": jnp.int32(batch["tokens"].shape[1]), "layers": states}


def init_cache(cfg, batch_size: int, seq_len: int):
    one = R.init_wkv_state(cfg, batch_size)
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one)
    return {"pos": jnp.int32(seq_len), "layers": stacked}


def decode_step(cfg, params, cache, token):
    x = B.embed(params["embed"], token)
    x = B.apply_norm(params["ln_in"], x, cfg.norm)

    def body(h, xs):
        lp, st = xs
        h, new_st = _block(cfg, lp, h, state=st)
        return h, new_st

    x, new_states = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    x = B.apply_norm(params["final_norm"], x, cfg.norm)
    logits = B.unembed(params["embed"], x)
    return logits, {"pos": cache["pos"] + 1, "layers": new_states}
