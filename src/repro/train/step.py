"""Training step factory: value_and_grad over the model loss + AdamW update.

The returned step is pjit-ready: callers pass in_shardings built from
sharding.rules; parameters FSDP+TP shard, moments follow parameters (ZeRO-1),
gradients reduce over (pod, data) implicitly via GSPMD.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.optim import AdamWConfig, adamw_init, adamw_update

TrainState = Dict[str, Any]  # {"params": ..., "opt": {m, v, step}}


def make_train_state(model, key, max_seq: int = 4096) -> TrainState:
    params = model.init(key, max_seq=max_seq)
    return {"params": params, "opt": adamw_init(params)}


def make_train_step(model, opt_cfg: AdamWConfig = AdamWConfig(),
                    *, schedule: Optional[Callable] = None):
    loss_fn = model.train_loss

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        params = state["params"]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        lr_scale = schedule(state["opt"]["step"]) if schedule else 1.0
        new_params, new_opt, metrics = adamw_update(
            opt_cfg, params, grads, state["opt"], lr_scale)
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics

    return step
