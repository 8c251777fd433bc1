"""Serving driver: batched greedy decode with Erda-backed state snapshots.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_1p6b --tokens 32
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import make_batch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import scale_config
from repro.models import get_model
from repro.serving import ServeEngine


def serve(arch="olmo_1b", scale="smoke", batch=4, prompt_len=64, tokens=16,
          snapshot_every=8, crash_at=None):
    """Greedy-decode ``tokens`` tokens for a synthetic batch.  Returns the
    (batch, tokens) token array and the engine's page-store stats."""
    cfg = scale_config(get_config(arch), scale)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), max_seq=prompt_len + tokens + 8)
    engine = ServeEngine(model, params, snapshot_every=snapshot_every)
    shape = ShapeConfig("serve", prompt_len, batch, "prefill")
    b = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape).items()}
    out = engine.generate(b, tokens, crash_at=crash_at)
    return out, engine.pages.stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "100m", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()
    out, stats = serve(args.arch, args.scale, args.batch, args.prompt_len,
                       args.tokens)
    print(f"[serve] generated {out.shape[1]} tokens × {out.shape[0]} requests; "
          f"{stats['snapshots']} snapshots, {stats['snapshot_bytes']} B")
    print(out[:, :12])


if __name__ == "__main__":
    main()
