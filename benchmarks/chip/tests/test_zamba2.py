"""The zamba2_7b configuration: its float32 reference against the program at
the program's smoke widths, its parameter count and decode costs against
hand counts, its two per-kind snapshot metrics, and a fault only a hybrid
shows (a snapshot with the KV right and the Mamba2 state stale) reading
``correct`` false."""
import math
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import spec
from metrics import snapshot_kv_MB, snapshot_state_MB
from repro.serving.kv_store import ErdaKVPageStore
from tiny import tiny_cell, tiny_config
from weights import make_init, seed_key

CONFIG = spec.load_json(spec.HERE / "configs" / "zamba2_7b.json")


def test_reference_matches_program_prefill_and_decode():
    """Float32 program and reference: what differs is summation order and
    the program's chunked SSD against the reference's stepped recurrence."""
    config = tiny_config("zamba2_7b")
    config["model"]["dtype"] = "float32"
    assert config["model"]["n_layers"] == 12  # both hybrid layers, 6 and 11
    model = run.program_model(config)
    params = make_init(model.init_abstract(64), config["init"])(seed_key(7))
    V = config["model"]["vocab_size"]
    prompt = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 16)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache = jax.jit(model.prefill)(params, {"tokens": prompt})
        seq, tokens = [logits[:, 0]], prompt
        for _ in range(5):
            tok = jnp.argmax(seq[-1], -1).astype(jnp.int32)[:, None]
            tokens = jnp.concatenate([tokens, tok], axis=1)
            logits, cache = jax.jit(model.decode_step)(params, cache, tok)
            seq.append(logits[:, 0])
        ref = spec.family(config).logits(params, tokens, config["model"])
    got = np.stack([np.asarray(s) for s in seq], axis=1)
    np.testing.assert_allclose(got, np.asarray(ref[:, 15:]), atol=2e-4,
                               rtol=2e-4)


def test_hand_counts():
    m = CONFIG["model"]
    fam = spec.family(CONFIG)
    mamba = 3584 * (7168 + 7424 + 112) + 4 * 7424 + 7424 + 3 * 112 + 7168 \
        + 7168 * 3584 + 3584          # in, conv + bias, A_log D dt_bias, norms, out
    shared = 3 * 7168 * 7168 + 7168 * 3584 + 3 * 3584 * 14336 + 7168 + 3584
    call = 3584 * 3584 + 128 * (3584 + 2 * 14336)
    hand = 12 * mamba + 2 * shared + 2 * call + 32000 * 3584 + 3584
    assert fam.param_count(m) == hand == 1_757_853_120
    shapes = run.program_model(CONFIG).init_abstract(256)
    assert sum(math.prod(s.shape) for s in jax.tree.leaves(shapes)) == hand
    # rewritten state at batch 4: f32 SSM state and bf16 conv windows
    state = 12 * 4 * (112 * 64 * 64 * 4 + 3 * 7424 * 2)
    assert fam.state_bytes(m, 4) == state == 90_218_496
    flops, moved = fam.decode_cost(m, 4, 200)
    f32 = 12 * 3 * 112
    kv = 2 * 2 * 4 * 32 * 224 * 2 * 200   # 2 calls' K and V, 200 positions
    assert moved == pytest.approx(2 * hand + 2 * f32 + 2 * state + kv)
    per_tok = 12 * (3584 * 14704 + 7168 * 3584) + 2 * (
        3 * 7168 * 7168 + 7168 * 3584 + 3 * 3584 * 14336 + call) + 32000 * 3584
    recur = 12 * 5 * 112 * 64 * 64
    assert flops == pytest.approx(4 * (2 * per_tok + 2 * 4 * 7168 * 200
                                       + recur))
    assert 3.5e9 < moved < 3.8e9


def test_snapshot_metrics_read_counters_or_nothing():
    ctx = types.SimpleNamespace(counters={
        "snapshots": 4, "snapshot_bytes": 600e6,
        "snapshot_state_bytes": 360e6, "snapshot_kv_bytes": 240e6})
    assert snapshot_state_MB.read(ctx) == pytest.approx(90.0)
    assert snapshot_kv_MB.read(ctx) == pytest.approx(60.0)
    parent = types.SimpleNamespace(counters={"snapshots": 4,
                                             "snapshot_bytes": 600e6})
    assert snapshot_state_MB.read(parent) is None
    assert snapshot_kv_MB.read(parent) is None


def _stale_mamba_state(monkeypatch):
    """Every snapshot after a session's first writes the current K/V and
    position with the first snapshot's Mamba2 state."""
    inner = ErdaKVPageStore.snapshot_cache
    first = {}

    def snapshot(self, seq_id, cache):
        old = first.setdefault((id(self), seq_id), cache)
        return inner(self, seq_id, {**cache, "mamba": old["mamba"]})
    monkeypatch.setattr(ErdaKVPageStore, "snapshot_cache", snapshot)


def test_stale_mamba_state_snapshot_reads_incorrect(monkeypatch):
    cell = tiny_cell("zamba2_7b", "snap8")
    run.T_START = time.perf_counter()
    good = run.run_cell(cell, 2**40 + 9, 1.0, False, need_tpu=False)
    assert good["correct"] is True, good["checks"]
    _stale_mamba_state(monkeypatch)
    run.T_START = time.perf_counter()
    broken = run.run_cell(cell, 2**40 + 9, 1.0, False, need_tpu=False)
    assert broken["correct"] is False
    assert broken["checks"]["snapshot_readback_bad"]["value"] > 0


def test_fp8_control_reads_incorrect():
    """The control, the reference in fp8 in the program's place, fails the
    limit the program passes (the tiny cell's limit, 0.05)."""
    cell = tiny_cell("zamba2_7b", "nosnap")
    for seed in (1, 2):
        run.T_START = time.perf_counter()
        r = run.run_cell(cell, seed, 0.5, False, need_tpu=False,
                         controls=["fp8"])
        limit = r["checks"]["logit_gap"]["limit"]
        assert r["checks"]["logit_gap"]["value"] <= limit
        assert r["controls"]["fp8"] > limit
