"""Erda-backed KV-cache page store for serving (DESIGN.md §2).

Decode-time KV pages / SSM state snapshots are Erda objects: appended with one
one-sided write each, page-table entries are the 8-byte atomic words, and a
preempted host's torn page is detected by CRC at fetch and falls back to the
previous snapshot.  The log cleaner doubles as page eviction/compaction.
Repeat fetches of a sequence's pages ride the client location cache: the
snapshot that wrote a page warmed the cache with its hash-table word, so the
decode-time re-fetch speculates (neighborhood + object on one doorbell) and
validates by word compare — a failover drops the hints via ``reconnect()``.

The store behind the page interface is pluggable: by default pages are sharded
across an ``ErdaCluster`` (consistent-hash key routing spreads sequences over
shards, so page traffic scales with shard count and a preempted shard recovers
independently); pass any ``make_store(...)`` object to override — e.g. a
single ``ErdaStore`` for the smallest deployments."""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.serialization import (encoded_size, leaf_from_bytes,
                                           leaf_to_bytes)
from repro.core import ServerConfig, make_store
from repro.core.hashtable import splitmix64

#: per-shard geometry for the default serving cluster: the paper's 8 MiB
#: segments, and room on each of the two shards for the ~5 snapshots of a
#: full-width olmo_1b decode cache (batch 4, 256 slots: 128 MiB each) that
#: one preempted 32-token run writes.  The device is zero-filled lazily, so
#: the size costs host memory only as pages are written.
PAGE_SHARD_CONFIG = ServerConfig(device_size=1 << 30, table_capacity=1 << 14,
                                 n_heads=4, region_size=64 << 20,
                                 segment_size=8 << 20)


#: the leaves of a decode cache that only grow, a position at a time:
#: attention keys and values, their int8 scales, and the positions their
#: slots hold.  Every other leaf (an SSM or RWKV state, a conv window, the
#: decode position) is state that each step rewrites whole.
KV_LEAVES = frozenset({"k", "v", "k_scale", "v_scale", "kv_pos"})


def leaf_kind(path) -> str:
    """"kv" for an append-only attention-cache leaf, else "state"."""
    return "kv" if getattr(path[-1], "key", None) in KV_LEAVES else "state"


#: slots in one record of an append-only K/V leaf: 8 decode steps between
#: snapshots fill one block a sequence (2 MiB of olmo_1b's ``k`` at batch 4)
KV_BLOCK_SLOTS = 8

_M64 = 0xFFFFFFFFFFFFFFFF


def _page_key(seq_id: int, name: str, idx: int) -> int:
    """The record key of page (or block) ``idx`` of leaf ``name``: the same
    in every process (no salted ``hash()``), so placement follows the
    inputs."""
    h = splitmix64(seq_id & _M64) ^ zlib.crc32(name.encode())
    return splitmix64(splitmix64(h) ^ (idx & _M64)) | 1


def _slot_axes(leaves) -> Dict[str, int]:
    """Leaf name → slot axis of each K/V leaf (``k``, ``v`` and scales) that
    sits in a dict beside its ``kv_pos``, whose last axis is the slot axis:
    the leaf's slot axis is ``kv_pos``'s rank.  A leaf whose extent there
    is not ``kv_pos``'s is left out (paged whole)."""
    key = jax.tree_util.keystr
    pos = {key(p[:-1]): leaf.shape for p, leaf in leaves
           if getattr(p[-1], "key", None) == "kv_pos"}
    out = {}
    for p, leaf in leaves:
        at = pos.get(key(p[:-1]))
        if (leaf_kind(p) == "kv" and p[-1].key != "kv_pos" and at
                and len(at) < len(leaf.shape)
                and leaf.shape[len(at)] == at[-1]):
            out[key(p)] = len(at)
    return out


def _block_slots(shape, dtype, axis: int, limit: int) -> Optional[int]:
    """Slots a block of this leaf holds: ``KV_BLOCK_SLOTS``, halved until a
    block fits one record of ``limit`` bytes; None if one slot does not."""
    per_slot = (int(np.prod(shape, dtype=np.int64)) // max(shape[axis], 1)
                * np.dtype(dtype).itemsize)
    slots = KV_BLOCK_SLOTS
    while slots > 1 and slots * per_slot > limit:
        slots //= 2
    return slots if slots * per_slot <= limit else None


def _layout(leaves, limit: int) -> List[Optional[tuple]]:
    """Per leaf, ``(axis, slots)`` if it is stored as slot blocks, else None
    (stored whole, cut into record-sized pages)."""
    axes = _slot_axes(leaves)
    out = []
    for p, leaf in leaves:
        axis = axes.get(jax.tree_util.keystr(p))
        slots = (None if axis is None
                 else _block_slots(leaf.shape, leaf.dtype, axis, limit))
        out.append(None if slots is None else (axis, slots))
    return out


def _block(ndim: int, axis: int, b: int, slots: int) -> tuple:
    """Index of block ``b`` along ``axis``."""
    return ((slice(None),) * axis + (slice(b * slots, (b + 1) * slots),)
            + (slice(None),) * (ndim - axis - 1))


@functools.partial(jax.jit, static_argnames=("axis", "slots"))
def _changed_blocks(new, old, *, axis: int, slots: int):
    """One flag per block of ``slots`` slots along ``axis``: does any bit of
    ``new`` differ from ``old`` there.  Floats compare as their bit
    patterns, so -0.0 differs from 0.0 and a NaN equals itself."""
    if jnp.issubdtype(new.dtype, jnp.floating):
        u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[new.dtype.itemsize]
        new = jax.lax.bitcast_convert_type(new, u)
        old = jax.lax.bitcast_convert_type(old, u)
    per_slot = jnp.any(new != old, axis=tuple(
        i for i in range(new.ndim) if i != axis))
    n = per_slot.shape[0]
    n_blocks = -(-n // slots)
    per_slot = jnp.pad(per_slot, (0, n_blocks * slots - n))
    return per_slot.reshape(n_blocks, slots).any(axis=1)


@functools.partial(jax.jit, static_argnames=("axis", "size"))
def _slice(leaf, start, *, axis: int, size: int):
    return jax.lax.dynamic_slice_in_dim(leaf, start, size, axis)


def _comparable(new, old) -> bool:
    """``old`` can stand for the blocks the store holds of ``new``: same
    shape and dtype, and a dtype the device compares exactly (64-bit host
    arrays would be narrowed on the way in)."""
    return (old is not None and old.shape == new.shape
            and old.dtype == new.dtype and np.dtype(new.dtype).itemsize <= 4)


class ErdaKVPageStore:
    def __init__(self, store=None, *, n_shards: int = 2, replication: int = 1):
        """``replication=2`` mirrors every page write to a ring-successor
        backup replica (repro.core.replication), so a preempted host losing a
        shard's NVM no longer loses that shard's KV pages — failover promotes
        the backup and decode resumes from the mirrored snapshots."""
        self.store = store or make_store("erda-cluster", n_shards=n_shards,
                                         replication=replication,
                                         cfg=PAGE_SHARD_CONFIG)
        self.counters = {"snapshots": 0, "snapshot_bytes": 0,
                         "snapshot_state_bytes": 0, "snapshot_kv_bytes": 0,
                         "snapshot_kv_blocks_written": 0,
                         "snapshot_kv_blocks_kept": 0, "restores": 0}
        # per seq_id, the K/V leaves (by name) of the cache handed to its
        # last acknowledged snapshot: the blocks the store holds
        self._last: Dict[int, Dict[str, object]] = {}

    def put_page(self, seq_id: int, name: str, idx: int, array) -> None:
        self.store.write(_page_key(seq_id, name, idx), leaf_to_bytes(array))

    def get_page(self, seq_id: int, name: str, idx: int) -> Optional[np.ndarray]:
        raw = self.store.read(_page_key(seq_id, name, idx))
        return None if raw is None else leaf_from_bytes(raw)

    def get_pages(self, seq_id: int, name: str,
                  idxs: Sequence[int]) -> List[Optional[np.ndarray]]:
        """Multi-page fetch: one doorbell-batched ``multi_read`` over the
        backing store (per-shard sub-batches on a cluster) instead of one
        round trip per page — the decode-time fill path for a sequence."""
        raws = self.store.multi_read([_page_key(seq_id, name, i) for i in idxs])
        return [None if raw is None else leaf_from_bytes(raw) for raw in raws]

    def drop_page(self, seq_id: int, name: str, idx: int) -> None:
        self._last.pop(seq_id, None)  # the record may be a snapshot's block
        self.store.delete(_page_key(seq_id, name, idx))

    # ------------------------------------------------- cache snapshot/restore
    def snapshot_cache(self, seq_id: int, cache) -> int:
        """Persist a whole decode cache pytree in one batched multi_write (2
        doorbells per shard), not one write per leaf; returns the records
        written.

        An append-only K/V leaf beside its ``kv_pos`` is stored as blocks of
        ``KV_BLOCK_SLOTS`` slots, one record each.  Against the cache handed
        to this ``seq_id``'s last snapshot, a device compare flags the
        blocks whose bits changed, and only those are copied to the host and
        written: the others are committed records with the same bytes.
        With no such cache (a session's first snapshot, or the first after
        a restore) every block is written.  Every other leaf is written
        whole, split into record-sized pages, since a record never spans a
        log segment.  Counts the bytes of each kind of leaf (``leaf_kind``)
        besides the total, and the K/V blocks written and kept."""
        with obs.span("pages.snapshot", seq_id=seq_id) as sp:
            limit = self.store.max_value_bytes
            leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
            # dropped until this snapshot is acknowledged: a failed write
            # leaves the store's blocks unknown
            last = self._last.pop(seq_id, {})
            items, blocked = [], {}
            by_kind = {"state": 0, "kv": 0}
            written = kept = 0
            for (path, leaf), blocks in zip(leaves, _layout(leaves, limit)):
                kind = leaf_kind(path)
                name = jax.tree_util.keystr(path)
                if blocks is None:
                    with obs.span("pages.to_host", kind=kind) as th:
                        host = np.asarray(leaf)
                        th.set(nbytes=host.nbytes)
                    with obs.span("pages.encode", nbytes=host.nbytes,
                                  kind=kind):
                        blob = leaf_to_bytes(host)
                        items += [(_page_key(seq_id, name, i),
                                   blob[off : off + limit])
                                  for i, off in enumerate(range(0, len(blob),
                                                                limit))]
                    by_kind[kind] += len(blob)
                    continue
                axis, slots = blocks
                n = leaf.shape[axis]
                n_blocks = -(-n // slots)
                blocked[name] = leaf
                old = last.get(name)
                with obs.span("pages.to_host", kind=kind) as th:
                    if _comparable(leaf, old):
                        with obs.span("pages.diff", kind=kind):
                            dirty = np.flatnonzero(np.asarray(_changed_blocks(
                                leaf, old, axis=axis, slots=slots)))
                        parts = [_slice(leaf, int(b) * slots, axis=axis,
                                        size=min(slots, n - int(b) * slots))
                                 for b in dirty]
                        for part in parts:
                            part.copy_to_host_async()
                        parts = [np.asarray(part) for part in parts]
                    else:
                        host = np.asarray(leaf)
                        dirty = range(n_blocks)
                        parts = [host[_block(host.ndim, axis, b, slots)]
                                 for b in dirty]
                    th.set(nbytes=sum(part.nbytes for part in parts))
                with obs.span("pages.encode", kind=kind) as enc:
                    vals = [part.tobytes() for part in parts]
                    items += [(_page_key(seq_id, name, int(b)), v)
                              for b, v in zip(dirty, vals)]
                    enc.set(nbytes=sum(map(len, vals)))
                by_kind[kind] += sum(map(len, vals))
                written += len(vals)
                kept += n_blocks - len(vals)
            self.store.multi_write(items)
            self._last[seq_id] = blocked
            nbytes = sum(len(v) for _, v in items)
            self.counters["snapshots"] += 1
            self.counters["snapshot_bytes"] += nbytes
            self.counters["snapshot_state_bytes"] += by_kind["state"]
            self.counters["snapshot_kv_bytes"] += by_kind["kv"]
            self.counters["snapshot_kv_blocks_written"] += written
            self.counters["snapshot_kv_blocks_kept"] += kept
            sp.set(nbytes=nbytes, blocks_written=written, blocks_kept=kept)
            return len(items)

    def restore_cache(self, seq_id: int, template):
        """The cache pytree last snapshotted for ``seq_id``, as host arrays
        shaped like ``template`` (arrays or ShapeDtypeStructs), or None if a
        record is missing.  One batched multi_read fetches every block and
        page of every leaf; a K/V leaf's blocks are copied into place in
        one array.  The next snapshot of ``seq_id`` writes every block."""
        with obs.span("pages.restore", seq_id=seq_id) as sp:
            self._last.pop(seq_id, None)
            limit = self.store.max_value_bytes
            leaves = jax.tree_util.tree_flatten_with_path(template)[0]
            layout = _layout(leaves, limit)
            counts = [-(-encoded_size(leaf.shape, leaf.dtype) // limit)
                      if blocks is None else -(-leaf.shape[blocks[0]]
                                               // blocks[1])
                      for (_, leaf), blocks in zip(leaves, layout)]
            raws = self.store.multi_read(
                [_page_key(seq_id, jax.tree_util.keystr(path), i)
                 for (path, _), n in zip(leaves, counts) for i in range(n)])
            if any(raw is None for raw in raws):
                return None
            out, at = [], 0
            for (_, leaf), blocks, n in zip(leaves, layout, counts):
                mine = raws[at : at + n]
                at += n
                with obs.span("pages.decode") as dec:
                    if blocks is None:
                        arr = leaf_from_bytes(b"".join(mine)).astype(
                            leaf.dtype, copy=False)
                    else:
                        axis, slots = blocks
                        arr = np.empty(leaf.shape, leaf.dtype)
                        for b, raw in enumerate(mine):
                            dst = arr[_block(arr.ndim, axis, b, slots)]
                            dst[...] = np.frombuffer(
                                raw, leaf.dtype).reshape(dst.shape)
                    out.append(arr)
                    dec.set(nbytes=sum(map(len, mine)))
            self.counters["restores"] += 1
            sp.set(nbytes=sum(len(raw) for raw in raws))
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(template), out)

    def compact(self) -> None:
        """Page eviction/compaction = the paper's lock-free log cleaning,
        swept across every shard of the backing store."""
        self.store.maybe_clean()

    # ----------------------------------------------------------- availability
    def fail_shard(self, shard: int) -> None:
        """Simulate a serving host losing a page shard's NVM.  The next
        snapshot of every sequence writes all its blocks again: the lost
        shard may have held some."""
        self._last.clear()
        self.store.fail_shard(shard)

    def failover(self, shard: int):
        """Promote the shard's mirrored backup; pages keep serving."""
        return self.store.failover(shard)

    @property
    def stats(self):
        """Backing-store op counters — includes the location cache's
        ``spec_hits`` / ``spec_misses`` / ``spec_invalidations``, i.e. how
        often page re-fetches collapsed to one doorbell — plus this page
        store's ``snapshots`` / ``snapshot_bytes`` (split into
        ``snapshot_state_bytes`` and ``snapshot_kv_bytes``) / ``restores``."""
        return {**self.store.stats, **self.counters}
