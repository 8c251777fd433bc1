"""Erda-protocol checkpointing: atomic commit, torn-write fallback, restart,
elastic resharding, straggler semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ErdaCheckpointManager
from repro.core import ErdaStore, ServerConfig

pytestmark = pytest.mark.slow  # JAX model/train lane; excluded from tier-1


def small_state(seed=0, scale=1.0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w1": jax.random.normal(k, (64, 128)) * scale,
                   "emb": {"table": jax.random.normal(k, (100, 32)) * scale}},
        "opt": {"m": {"a": jnp.zeros((64,))}, "step": jnp.int32(7)},
    }


def small_mgr():
    return ErdaCheckpointManager(ErdaStore(ServerConfig(
        device_size=128 << 20, table_capacity=1 << 12, n_heads=2,
        region_size=8 << 20, segment_size=1 << 20)), shard_bytes=4096)


def assert_state_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_save_restore_roundtrip():
    mgr = small_mgr()
    state = small_state()
    mgr.save(10, state)
    step, got = mgr.restore(state)
    assert step == 10
    assert_state_equal(state, got)


def test_second_checkpoint_supersedes():
    mgr = small_mgr()
    s1, s2 = small_state(1), small_state(2, scale=2.0)
    mgr.save(10, s1)
    mgr.save(20, s2)
    step, got = mgr.restore(s1)
    assert step == 20
    assert_state_equal(s2, got)


def test_writer_crash_before_commit_keeps_previous():
    """The paper's guarantee, at checkpoint granularity: a writer that dies
    mid-shard never corrupts the committed checkpoint."""
    mgr = small_mgr()
    s1, s2 = small_state(1), small_state(2, scale=3.0)
    mgr.save(10, s1)
    with pytest.raises(RuntimeError, match="injected"):
        mgr.save(20, s2, fail_after_shards=2)
    step, got = mgr.restore(s1)
    assert step == 10          # step-20 manifest never flipped
    assert_state_equal(s1, got)
    # and a later successful save works on the same store
    mgr.save(30, s2)
    step, got = mgr.restore(s1)
    assert step == 30
    assert_state_equal(s2, got)


def test_torn_manifest_falls_back_to_old_version():
    mgr = small_mgr()
    s1, s2 = small_state(1), small_state(2, scale=4.0)
    mgr.save(10, s1)
    # shards of step 20 written fine, but the MANIFEST data write tears
    leaves_written = mgr.save(20, s2)
    assert leaves_written > 0
    from repro.nvmsim.device import TornWrite
    mgr.store.dev.fault.arm(countdown=0, fraction=0.4)
    import json
    with pytest.raises(TornWrite):
        mgr.store.write(0x3A5F00D, json.dumps({"step": 99, "entries": []}).encode())
    step, got = mgr.restore(s1)
    assert step == 20          # torn step-99 manifest → CRC fallback to 20
    assert_state_equal(s2, got)


def test_server_crash_recovery_then_restore():
    mgr = small_mgr()
    s1 = small_state(1)
    mgr.save(10, s1)
    stats = mgr.crash_recover()
    assert stats["removed"] == 0
    step, got = mgr.restore(s1)
    assert step == 10
    assert_state_equal(s1, got)


def test_training_restart_resumes(tmp_path):
    """End-to-end: train → checkpoint → 'kill' → resume → identical continuation."""
    from repro.launch.train import train
    mgr = small_mgr()
    state_a, losses_a, _ = train(arch="olmo_1b", scale="smoke", steps=6,
                                 batch=2, seq=32, ckpt_every=4, ckpt_mgr=mgr,
                                 log_every=0)
    # fresh process analogue: resume from the same store (checkpoint @ step 4
    # → re-executes steps 5..6 with identical data + state)
    state_b, losses_b, _ = train(arch="olmo_1b", scale="smoke", steps=6,
                                 batch=2, seq=32, ckpt_every=0, resume=True,
                                 ckpt_mgr=mgr, log_every=0)
    assert len(losses_b) == 2
    assert losses_b == pytest.approx(losses_a[-2:], rel=1e-4)


def test_elastic_reshard_restore():
    """The newest checkpoint restores onto a mesh, sharded by the rules."""
    from jax.sharding import Mesh
    from repro.launch.elastic import reshard_restore
    from repro.optim import adamw_init
    params = small_state()["params"]
    state = {"params": params, "opt": adamw_init(params)}
    mgr = small_mgr()
    mgr.save(10, state)
    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    step, got = reshard_restore(mgr, state, mesh)
    assert step == 10
    assert_state_equal(state, got)
    for leaf in jax.tree.leaves(got["params"]):
        assert leaf.sharding.mesh.shape == mesh.shape


def test_straggler_never_blocks_readers():
    """A slow writer holds no locks: concurrent readers always see the old
    committed state while a new checkpoint is being written."""
    mgr = small_mgr()
    s1, s2 = small_state(1), small_state(2, scale=5.0)
    mgr.save(10, s1)
    # write half the shards of step 20 ("straggler stalls here")
    try:
        mgr.save(20, s2, fail_after_shards=4)
    except RuntimeError:
        pass
    for _ in range(5):  # readers during the stall
        step, got = mgr.restore(s1)
        assert step == 10
        assert_state_equal(s1, got)
