"""Decode-cache snapshots larger than a log segment: the page store splits
each leaf into record-sized pages and restores it byte for byte."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ServerConfig, make_store
from repro.serving.kv_store import ErdaKVPageStore

SMALL_SEGMENTS = ServerConfig(device_size=16 << 20, table_capacity=1 << 10,
                              n_heads=2, region_size=1 << 20,
                              segment_size=64 << 10)


def _cache():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((4, 2, 96, 4, 32)).astype(np.float32)  # 384 KiB
    return {"pos": jnp.int32(7),
            "full": {"k": jnp.asarray(k, jnp.bfloat16),              # 192 KiB
                     "v": jnp.asarray(k),
                     "kv_pos": jnp.arange(96, dtype=jnp.int32)}}


@pytest.mark.parametrize("scheme,kw", [("erda", {}),
                                       ("erda-cluster", {"n_shards": 2})])
def test_leaf_larger_than_segment_roundtrips(scheme, kw):
    pages = ErdaKVPageStore(make_store(scheme, cfg=SMALL_SEGMENTS, **kw))
    cache = _cache()
    leaves = jax.tree.leaves(cache)
    assert max(leaf.nbytes for leaf in leaves) > 5 * SMALL_SEGMENTS.segment_size
    n_pages = pages.snapshot_cache(3, cache)
    assert n_pages > len(leaves)
    # a ShapeDtypeStruct template suffices: nothing is read off the device
    got = pages.restore_cache(3, jax.eval_shape(lambda: cache))
    for want, have in zip(leaves, jax.tree.leaves(got)):
        assert have.dtype == want.dtype and have.shape == want.shape
        assert np.asarray(have).tobytes() == np.asarray(want).tobytes()
    assert got["full"]["k"].dtype == jnp.bfloat16
    assert pages.stats["snapshots"] == 1 and pages.stats["restores"] == 1
    assert pages.stats["snapshot_bytes"] > sum(leaf.nbytes for leaf in leaves)


def test_restore_without_snapshot_is_none():
    pages = ErdaKVPageStore(make_store("erda", cfg=SMALL_SEGMENTS))
    assert pages.restore_cache(9, _cache()) is None
    assert pages.stats["restores"] == 0
