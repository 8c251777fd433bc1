#!/usr/bin/env python3
"""Smoke run of the served path on one TPU chip.

    python chip_smoke.py [--seed 0]

One process, no subprocess.  Phases, in order:

1. device: JAX must see a TPU; there is no CPU fallback.
2. timing: olmo_1b at full width (random weights from a fixed key):
   parameter init, compile, prefill and per-token decode, timed with
   ``block_until_ready``.
3. serve: ``repro.launch.serve.serve`` decodes the same model with Erda
   page-store snapshots, once clean and once preempted mid-decode.  The
   preempted run restores its decode cache and tokens from the page store
   and must emit the same tokens.
4. crc: the compiled Pallas CRC kernel verifies a batch of 1 KiB Erda
   records against their header CRCs and ``zlib.crc32``, and flags exactly
   the records with a flipped bit.

Lines tagged ``[smoke reading]`` are what this run saw, not a benchmark.  Any
failed check exits non-zero; the last line of a passing run is the JSON
object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import zlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH, BATCH, PROMPT, TOKENS, SNAPSHOT_EVERY, CRASH_AT = (
    "olmo_1b", 4, 128, 32, 8, 20)
N_RECORDS, VALUE_BYTES, N_FLIPS = 4096, 1005, 8


def reading(what: str, value) -> None:
    print(f"[smoke reading] {what}: {value}", flush=True)


def fail(why: str) -> None:
    print(f"[smoke] FAILED: {why}", file=sys.stderr, flush=True)
    sys.exit(1)


def tpu_devices():
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX runs on {devices[0].platform!r}")
    return devices


def serve_phase(vocab: int) -> None:
    import numpy as np
    from repro.launch.serve import serve

    runs = {}
    for name, crash_at in (("clean", None), ("preempted", CRASH_AT)):
        t0 = time.perf_counter()
        tokens, stats = serve(ARCH, "full", BATCH, PROMPT, TOKENS,
                              snapshot_every=SNAPSHOT_EVERY, crash_at=crash_at)
        reading(f"serve {name} wall s (init+compile+decode+snapshots)",
                time.perf_counter() - t0)
        reading(f"serve {name} snapshots", stats["snapshots"])
        reading(f"serve {name} bytes snapshotted", stats["snapshot_bytes"])
        reading(f"serve {name} page-store reads", stats["reads"])
        runs[name] = (tokens, stats)
    (clean, clean_stats), (crashed, crash_stats) = runs["clean"], runs["preempted"]
    if clean.shape != (BATCH, TOKENS):
        fail(f"token array shape {clean.shape} != {(BATCH, TOKENS)}")
    if not np.array_equal(clean, crashed):
        fail("preempted run's tokens differ from the clean run's")
    if clean.min() < 0 or clean.max() >= vocab:
        fail(f"tokens outside [0, {vocab})")
    if clean_stats["restores"] != 0 or clean_stats["reads"] != 0:
        fail(f"clean run read from the page store: {clean_stats}")
    if crash_stats["restores"] != 1 or crash_stats["reads"] == 0:
        fail(f"preempted run did not restore from the page store: {crash_stats}")
    print(f"[smoke] serve: clean and preempted-then-restored {ARCH} tokens "
          f"equal, {clean.shape}", flush=True)


def timing_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.data import make_batch
    from repro.models import get_model

    model = get_model(get_config(ARCH))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        model.init(jax.random.PRNGKey(0), max_seq=PROMPT + TOKENS + 8))
    reading("params init s", time.perf_counter() - t0)
    shape = ShapeConfig("serve", PROMPT, BATCH, "prefill")
    batch = {k: jnp.asarray(v) for k, v in make_batch(model.cfg, shape).items()}
    t0 = time.perf_counter()
    prefill = jax.jit(model.prefill).lower(params, batch).compile()
    logits, cache = prefill(params, batch)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    decode = jax.jit(model.decode_step).lower(params, cache, token).compile()
    reading("prefill+decode compile s (cold unless the compile cache held "
            "them)", time.perf_counter() - t0)
    t0 = time.perf_counter()
    jax.block_until_ready(prefill(params, batch))
    reading(f"prefill s (batch {BATCH} x {PROMPT} tokens)",
            time.perf_counter() - t0)
    jax.block_until_ready(decode(params, cache, token))  # warm
    t0 = time.perf_counter()
    for _ in range(TOKENS):
        logits, cache = decode(params, cache, token)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(token)
    reading(f"decode s per token (batch {BATCH})",
            (time.perf_counter() - t0) / TOKENS)


def crc_phase(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import layout
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    values = rng.integers(0, 256, size=(N_RECORDS, VALUE_BYTES), dtype=np.uint8)
    keys = rng.integers(1, 2**63, size=N_RECORDS, dtype=np.uint64)
    recs = np.frombuffer(b"".join(
        layout.pack_record(int(k), v.tobytes()) for k, v in zip(keys, values)),
        np.uint8).reshape(N_RECORDS, -1).copy()
    if recs.shape[1] != 1024:
        fail(f"records are {recs.shape[1]} B, not 1024")
    header_crc = recs[:, 1:5].copy().view("<u4")[:, 0]
    recs[:, 1:5] = 0  # the CRC covers the record with its CRC field zeroed
    words = jax.device_put(recs.view("<u4"))
    compiled = ops.crc32_batch.lower(words).compile()
    if "tpu_custom_call" not in compiled.as_text():
        fail("the CRC kernel was not compiled for the TPU")
    got = np.asarray(compiled(words))
    want = np.array([zlib.crc32(r.tobytes()) for r in recs], np.uint32)
    if not (np.array_equal(got, header_crc) and np.array_equal(got, want)):
        fail(f"{int((got != want).sum())} kernel CRCs differ from zlib, "
             f"{int((got != header_crc).sum())} from the headers")
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(words))
    reading(f"crc kernel s ({N_RECORDS} x 1 KiB records)",
            time.perf_counter() - t0)

    flipped = np.sort(rng.choice(N_RECORDS, N_FLIPS, replace=False))
    body = np.r_[0, 5:recs.shape[1]]  # every byte but the zeroed CRC field
    mutated = recs.copy()
    for i in flipped:
        mutated[i, rng.choice(body)] ^= np.uint8(1 << rng.integers(8))
    caught = np.flatnonzero(
        np.asarray(compiled(jax.device_put(mutated.view("<u4")))) != header_crc)
    if not np.array_equal(caught, flipped):
        fail(f"bit flips in records {flipped.tolist()}, "
             f"kernel flagged {caught.tolist()}")
    reading("records checked", N_RECORDS)
    print(f"[smoke] crc: compiled kernel matched zlib on {N_RECORDS} records "
          f"and flagged exactly the {N_FLIPS} with a flipped bit", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = tpu_devices()
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    reading("device_kind", devices[0].device_kind)
    reading("compile cache", use_compile_cache())
    timing_phase()
    serve_phase(get_config(ARCH).vocab_size)
    crc_phase(args.seed)
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        reading("peak device bytes in use", stats["peak_bytes_in_use"])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
