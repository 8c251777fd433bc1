"""Model registry: uniform functional interface per architecture family.

get_model(cfg) → Model with:
  init(key, max_seq)            — parameters (no served family's depend on
                                  max_seq; callers may still pass it)
  train_loss(params, batch)     — scalar loss
  prefill(params, batch)        — (last_logits, cache)
  decode_step(params, cache, token)
  init_cache(batch, seq_len)    — empty cache with room for seq_len tokens
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax

from repro.configs.base import ModelConfig
from repro.models import hybrid, rwkv_model, transformer

#: ``ModelConfig.family`` → the module that implements it
FAMILIES = {"dense": transformer, "ssm": rwkv_model, "hybrid": hybrid}


def get_model(cfg: ModelConfig) -> SimpleNamespace:
    if cfg.family not in FAMILIES:
        raise ValueError(f"unknown family {cfg.family}")
    mod = FAMILIES[cfg.family]

    def init(key, max_seq: int = 4096):
        return mod.init_lm(cfg, key)

    def init_abstract(max_seq: int = 4096):
        return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), max_seq))

    return SimpleNamespace(
        cfg=cfg,
        init=init,
        init_abstract=init_abstract,
        train_loss=functools.partial(mod.train_loss, cfg),
        prefill=functools.partial(mod.prefill, cfg),
        decode_step=functools.partial(mod.decode_step, cfg),
        init_cache=functools.partial(mod.init_cache, cfg),
    )
