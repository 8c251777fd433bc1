"""A run with its timed path broken underneath reads ``correct`` false.

Each test skips only the harness's look for a chip and drives the rest of a
run (set-up, window, page-store read-back, token replay, reference) on a
tiny cell on the CPU, with one fault planted in the program's objects:

- ``state_unchanged``: the decode step returns the cache it was given;
- ``token_altered``: one row's logits are shifted where the step makes them;
- ``half_batch``: the step's second half of the batch is a copy of the
  first half's;
- ``stale_restore``: the page store restores a cache of zeros;
- ``lost_snapshot``: snapshots are acknowledged and never written;
- ``stale_snapshot``: every snapshot after a session's first writes the
  first one's cache again, with the new position (what a changed-pages
  snapshot that misses the changed pages would store).
Faults of the page store are planted in its class, so every store of the
window has them.  The exchange between chips has no fault to plant: every
cell is one chip.
The control (the fp8 reference in the program's place) is held to the same
limit and fails it too.
"""
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from repro.serving.kv_store import ErdaKVPageStore
from tiny import tiny_cell


def _state_unchanged(engine):
    inner = engine._decode
    engine._decode = lambda p, c, t: (inner(p, c, t)[0], c)


def _token_altered(engine):
    inner = engine._decode

    def step(p, c, t):
        logits, cache = inner(p, c, t)
        return logits.at[0].set(jnp.roll(logits[0], 1, axis=-1)), cache
    engine._decode = step


def _half_batch(engine):
    inner = engine._decode

    def step(p, c, t):
        logits, cache = inner(p, c, t)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]], axis=0), cache
    engine._decode = step


def _stale_restore(monkeypatch):
    inner = ErdaKVPageStore.restore_cache

    def restore(self, seq_id, template):
        got = inner(self, seq_id, template)
        return None if got is None else jax.tree.map(np.zeros_like, got)
    monkeypatch.setattr(ErdaKVPageStore, "restore_cache", restore)


def _lost_snapshot(monkeypatch):
    monkeypatch.setattr(ErdaKVPageStore, "snapshot_cache",
                        lambda self, seq_id, cache: 0)


def _stale_snapshot(monkeypatch):
    inner = ErdaKVPageStore.snapshot_cache
    first = {}

    def snapshot(self, seq_id, cache):
        old = first.setdefault((id(self), seq_id), cache)
        return inner(self, seq_id, {**old, "pos": cache["pos"]})
    monkeypatch.setattr(ErdaKVPageStore, "snapshot_cache", snapshot)


ENGINE_FAULTS = {"state_unchanged": ("olmo_1b", "nosnap", _state_unchanged),
                 "token_altered": ("olmo_1b", "nosnap", _token_altered),
                 "half_batch": ("rwkv6_1p6b", "nosnap", _half_batch)}
STORE_FAULTS = {"stale_restore": ("olmo_1b", "preempt", _stale_restore),
                "lost_snapshot": ("rwkv6_1p6b", "preempt", _lost_snapshot),
                "stale_snapshot": ("olmo_1b", "snap8", _stale_snapshot)}
FAULTS = {**ENGINE_FAULTS, **STORE_FAULTS}


def _run(cell, monkeypatch, fault=None):
    if fault is not None:
        from repro.serving import engine as engine_mod
        init = engine_mod.ServeEngine.__init__

        def planted(self, *a, **kw):
            init(self, *a, **kw)
            self.plant = fault

        # plant once the harness has installed the window's page store
        generate = engine_mod.ServeEngine.generate

        def generate_with_fault(self, batch, n, *, seq_id=0, crash_at=None):
            if seq_id >= 0 and getattr(self, "plant", None):
                self.plant(self)
                self.plant = None
            return generate(self, batch, n, seq_id=seq_id, crash_at=crash_at)
        monkeypatch.setattr(engine_mod.ServeEngine, "__init__", planted)
        monkeypatch.setattr(engine_mod.ServeEngine, "generate",
                            generate_with_fault)
    run.T_START = time.perf_counter()
    return run.run_cell(cell, 2**40 + 5, 1.0, False, need_tpu=False)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_incorrect(fault, monkeypatch):
    config, traffic, plant = FAULTS[fault]
    cell = tiny_cell(config, traffic)
    assert _run(cell, monkeypatch)["correct"] is True
    if fault in STORE_FAULTS:
        plant(monkeypatch)  # the warm-up's stand-in store is not this class
        broken = _run(cell, monkeypatch)
    else:
        broken = _run(cell, monkeypatch, plant)
    assert broken["correct"] is False, broken["checks"]


@pytest.mark.parametrize("config", ["olmo_1b", "rwkv6_1p6b"])
def test_fp8_control_reads_incorrect(config):
    """The control, the reference in fp8 in the program's place, fails the
    limit the program passes, on three seeds (the tiny cell's limit, 0.05)."""
    cell = tiny_cell(config, "nosnap")
    for seed in (1, 2, 3):
        run.T_START = time.perf_counter()
        r = run.run_cell(cell, seed, 0.5, False, need_tpu=False,
                         controls=["fp8"])
        limit = r["checks"]["logit_gap"]["limit"]
        assert r["checks"]["logit_gap"]["value"] <= limit
        assert r["controls"]["fp8"] > limit


def test_no_tpu_exits_without_a_result():
    proc = subprocess.run(
        [sys.executable, str(run.HERE / "run.py"), "--workload",
         "olmo_1b.nosnap", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
