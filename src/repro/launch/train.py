"""End-to-end training driver with Erda checkpointing + restart.

    PYTHONPATH=src python -m repro.launch.train --arch olmo_1b --scale smoke \
        --steps 50 --ckpt-every 20

``--scale 100m`` trains a ~100M-param olmo-family model on synthetic
structured tokens (examples/train_lm.py drives this for a few hundred steps);
``--scale full`` uses the assigned config (needs real hardware).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import ErdaCheckpointManager
from repro.checkpoint.erda_ckpt import WriterCrash
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import SyntheticTokens
from repro.launch.compile_cache import use_compile_cache
from repro.models import get_model
from repro.optim import AdamWConfig, cosine_schedule
from repro.train import make_train_step
from repro.train.step import make_train_state


def scale_config(cfg, scale: str):
    if scale == "full":
        return cfg
    if scale == "smoke":
        return cfg.scaled_down()
    if scale == "100m":  # ~100M params, runnable on CPU for a few hundred steps
        return dataclasses.replace(
            cfg, n_layers=6, d_model=512, n_heads=8, n_kv_heads=8, head_dim=64,
            d_ff=2048, vocab_size=8192, attn_chunk=256, remat="none",
            tie_embeddings=False)  # untied head learns faster from small init
    raise ValueError(scale)


def train(arch="olmo_1b", scale="smoke", steps=50, batch=8, seq=128,
          ckpt_every=0, resume=False, ckpt_mgr=None, lr=3e-4, log_every=10,
          fail_ckpt_at=None):
    cfg = scale_config(get_config(arch), scale)
    model = get_model(cfg)
    step_fn = jax.jit(make_train_step(
        model, AdamWConfig(lr=lr),
        schedule=lambda s: cosine_schedule(s, warmup=20, total=max(steps, 100))))
    data = SyntheticTokens(cfg.vocab_size, seq, batch, seed=7)
    mgr = ckpt_mgr or ErdaCheckpointManager()
    start = 0
    state = None
    if resume:
        template = jax.eval_shape(
            lambda: make_train_state(model, jax.random.PRNGKey(0), max_seq=seq))
        got_step, got = mgr.restore(template)
        if got_step is not None:
            start, state = got_step, jax.tree.map(jnp.asarray, got)
            print(f"[train] resumed from Erda checkpoint @ step {start}")
    if state is None:
        state = make_train_state(model, jax.random.PRNGKey(0), max_seq=seq)

    shape = ShapeConfig("drv", seq, batch, "train")
    losses = []
    t0 = time.time()
    for s in range(start, steps):
        from repro.data import make_batch
        b = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, step=s).items()}
        state, metrics = step_fn(state, b)
        losses.append(float(metrics["loss"]))
        if log_every and (s + 1) % log_every == 0:
            print(f"[train] step {s+1}: loss {losses[-1]:.4f} "
                  f"({(time.time()-t0)/max(1,s+1-start):.2f}s/step)")
        if ckpt_every and (s + 1) % ckpt_every == 0:
            kwargs = {}
            if fail_ckpt_at is not None and (s + 1) == fail_ckpt_at:
                kwargs["fail_after_shards"] = 3
            try:
                mgr.save(s + 1, state, **kwargs)
            except WriterCrash as e:
                print(f"[train] checkpoint writer crashed @ step {s+1}: {e}")
    return state, losses, mgr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    use_compile_cache()
    _, losses, _ = train(args.arch, args.scale, args.steps, args.batch,
                         args.seq, args.ckpt_every, args.resume, lr=args.lr)
    print(f"[train] done: first loss {losses[0]:.4f} → last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
