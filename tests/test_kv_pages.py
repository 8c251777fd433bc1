"""Decode-cache snapshots larger than a log segment: the page store splits
each leaf into record-sized pages and restores it byte for byte.  Append-only
K/V leaves are stored as slot blocks, and a snapshot writes only the blocks
that changed since the session's last one."""
import dataclasses
import functools
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ServerConfig, make_store
from repro.checkpoint.serialization import encoded_size
from repro.core.layout import record_size
from repro.serving.kv_store import ErdaKVPageStore, _layout, _page_key

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
SNAP8 = json.loads((CHIP / "traffic" / "snap8.json").read_text())

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


# ------------------------------------------ append-only K/V as slot blocks
def _same_bits(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.asarray(x).shape == np.asarray(y).shape
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def _kv_blocks(cache, limit):
    """(name, axis, slots, blocks) of each leaf stored as slot blocks."""
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    return [(jax.tree_util.keystr(p), b[0], b[1], -(-a.shape[b[0]] // b[1]))
            for (p, a), b in zip(leaves, _layout(leaves, limit))
            if b is not None]


def _written_kept(pages):
    return (pages.counters["snapshot_kv_blocks_written"],
            pages.counters["snapshot_kv_blocks_kept"])


@pytest.mark.parametrize("arch", ["olmo_1b", "zamba2_7b"])
def test_delta_snapshots_read_back_bit_for_bit(arch):
    """A session snapshotting every 2 decode steps writes every K/V block
    once, then only the block the new slots fall in; the store reads back
    the cache handed to the last snapshot, bit for bit.  A restore makes
    the next snapshot write every block again, and a slot changed with its
    ``kv_pos`` left as it was is still found and written."""
    from repro.configs import get_config
    from repro.models import get_model
    from repro.serving import ServeEngine
    cfg = get_config(arch).scaled_down()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), max_seq=64)
    pages = ErdaKVPageStore(make_store("erda-cluster", n_shards=2,
                                       cfg=SMALL_SEGMENTS))
    handed, per_snapshot = [], []
    inner = pages.snapshot_cache

    def snapshot(seq_id, cache):
        before = _written_kept(pages)
        n = inner(seq_id, cache)
        handed.append(cache)
        per_snapshot.append(tuple(
            b - a for a, b in zip(before, _written_kept(pages))))
        return n

    pages.snapshot_cache = snapshot
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    ServeEngine(model, params, page_store=pages, snapshot_every=2).generate(
        {"tokens": tokens}, 13, seq_id=5)
    assert len(handed) == 6
    blocks = _kv_blocks(handed[-1], pages.store.max_value_bytes)
    n_blocks = sum(b[3] for b in blocks)
    assert blocks and n_blocks > len(blocks)
    # prompt 16 and 2 steps a snapshot: the new slots fall in one block a leaf
    assert per_snapshot == [(n_blocks, 0)] + [
        (len(blocks), n_blocks - len(blocks))] * 5
    template = jax.eval_shape(lambda: handed[-1])
    got = pages.restore_cache(5, template)
    assert _same_bits(got, handed[-1])

    pages.snapshot_cache(5, got)  # after a restore: every block again
    assert per_snapshot[-1] == (n_blocks, 0)

    name, axis, slots, _ = blocks[0]
    leaves, tree = jax.tree_util.tree_flatten_with_path(got)
    faulty = []
    for p, leaf in leaves:
        leaf = jnp.asarray(leaf)
        if jax.tree_util.keystr(p) == name:  # slot 3: block 0, long written
            at = (0,) * axis + (3,)
            leaf = leaf.at[at].set(leaf[at] + 1)
        faulty.append(leaf)
    faulty = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(got), faulty)
    pages.snapshot_cache(5, faulty)
    assert per_snapshot[-1] == (1, n_blocks - 1)
    assert _same_bits(pages.restore_cache(5, template), faulty)
    assert not _same_bits(faulty, got)


@pytest.mark.parametrize("heads,slots", [(2, 8), (8, 2), (16, 1),
                                         (32, None)])
def test_blocks_fit_one_record(heads, slots):
    """A block holds fewer than 8 slots where 8 would not fit one record
    (64 KiB less a header here; a slot is 8 KiB a head), and a leaf one slot
    of which does not fit is stored whole, in pages; each reads back."""
    pages = ErdaKVPageStore(make_store("erda", cfg=SMALL_SEGMENTS))
    k = np.random.default_rng(0).standard_normal((2, 24, heads, 256))
    cache = {"pos": jnp.int32(24),
             "kv": {"k": jnp.asarray(k, jnp.float32),
                    "kv_pos": jnp.arange(24, dtype=jnp.int32)}}
    limit = pages.store.max_value_bytes
    blocks = _kv_blocks(cache, limit)
    if slots is None:
        assert blocks == []
        k_records = -(-encoded_size(k.shape, np.float32) // limit)
    else:
        assert blocks == [("['kv']['k']", 1, slots, 24 // slots)]
        k_records = 24 // slots
    assert pages.snapshot_cache(1, cache) == k_records + 2  # pos, kv_pos
    assert _same_bits(pages.restore_cache(1, jax.eval_shape(lambda: cache)),
                      cache)


class _Tally:
    """Stand-in store that counts the bytes handed to ``multi_write``."""

    def __init__(self, segment_bytes):
        self.max_value_bytes = segment_bytes - record_size(0)
        self.written = []

    def multi_write(self, items):
        self.written.append(sum(len(v) for _, v in items))


def _cell_model(name):
    """The program's model at a benchmark configuration's widths, and that
    configuration's page store."""
    from repro.configs import get_config
    from repro.models import get_model
    config = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    skip = set(config.get("reference_only", ()))
    cfg = dataclasses.replace(get_config(config["program_arch"]), **{
        k: v for k, v in config["model"].items() if k not in skip})
    return get_model(cfg), config["page_store"]


@functools.partial(jax.jit, static_argnames=("axes",))
def _decode_slots(cache, start, axes):
    """What ``snapshot_every`` decode steps write into the K/V leaves: new
    slots from ``start``, and their positions.  State leaves are written
    whole at every snapshot whatever they hold, so they stay."""
    axes = dict(axes)
    leaves, tree = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for p, leaf in leaves:
        name = jax.tree_util.keystr(p)
        if name in axes:
            new = jnp.ones(leaf.shape[:axes[name]] + (SNAP8["snapshot_every"],)
                           + leaf.shape[axes[name] + 1:], leaf.dtype)
            leaf = jax.lax.dynamic_update_slice_in_dim(leaf, new, start,
                                                       axes[name])
        elif getattr(p[-1], "key", None) == "kv_pos":
            new = start + jnp.arange(SNAP8["snapshot_every"], dtype=leaf.dtype)
            new = jnp.broadcast_to(new, leaf.shape[:-1] + new.shape)
            leaf = jax.lax.dynamic_update_slice_in_dim(leaf, new, start,
                                                       leaf.ndim - 1)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.mark.parametrize("cell", ["olmo_1b", "rwkv6_1p6b", "zamba2_7b"])
def test_snap8_session_fits_one_shard(cell):
    """What one session of a snap8 cell hands to ``multi_write`` (16
    snapshots of a batch-4, prompt-128 cache, 8 steps apart) stays under
    one shard's NVM, so no placement of its records can fill a shard."""
    model, store_cfg = _cell_model(cell)
    B, P = SNAP8["batch"], SNAP8["prompt_len"]
    n_snapshots = SNAP8["new_tokens"] // SNAP8["snapshot_every"]
    template = jax.eval_shape(lambda: model.init_cache(B, P))
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), template)
    store = _Tally(store_cfg["segment_bytes"])
    pages = ErdaKVPageStore(store)
    leaves = jax.tree_util.tree_flatten_with_path(template)[0]
    axes = tuple((n, a) for n, a, _, _ in _kv_blocks(
        template, store.max_value_bytes))
    for i in range(n_snapshots):
        pages.snapshot_cache(1, cache)
        cache = _decode_slots(cache, P + i * SNAP8["snapshot_every"], axes)
    total = sum(store.written)
    assert len(store.written) == n_snapshots
    assert total < store_cfg["nvm_bytes_per_shard"]
    if cell == "olmo_1b":  # paged whole, 16 snapshots would be 2 GiB
        whole = sum(s.size * s.dtype.itemsize for _, s in leaves)
        assert n_snapshots * whole >= store_cfg["nvm_bytes_per_shard"]
        assert total <= 250 << 20
    if cell == "rwkv6_1p6b":  # no K/V: every snapshot whole
        assert not axes and len(set(store.written)) == 1


def test_page_key_is_the_same_in_every_process():
    """Record keys (and so shard placement) do not follow the salted
    ``hash()`` of a str: two processes with different hash seeds agree."""
    code = ("from repro.serving.kv_store import _page_key; "
            "print(_page_key(7, \"['full']['k']\", 3), hash('salt'))")
    outs = []
    for seed in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])}
        outs.append(subprocess.run([sys.executable, "-c", code], env=env,
                                   capture_output=True, text=True,
                                   check=True).stdout.split())
    assert outs[0][1] != outs[1][1]  # the hash seeds did differ
    assert outs[0][0] == outs[1][0] == str(_page_key(7, "['full']['k']", 3))
