"""Kernel micro-benchmarks (interpret mode on CPU — correctness-path timing;
the derived column reports per-call work, not TPU wall time)."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, iters=3):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def bench_kernels() -> List[Dict]:
    rows = []
    rng = np.random.default_rng(0)

    data = jnp.asarray(rng.integers(0, 2**32, size=(512, 256), dtype=np.uint32))
    t_k = _time(lambda d: ops.crc32_batch(d, interpret=True), data)
    t_r = _time(jax.jit(ref.crc32_ref), data)
    rows.append({"figure": "kernel", "name": "crc32_batch 512x1KiB",
                 "pallas_us": round(t_k * 1e6, 1), "ref_us": round(t_r * 1e6, 1),
                 "bytes": int(data.size * 4)})
    return rows
