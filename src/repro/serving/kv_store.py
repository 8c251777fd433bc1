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

from typing import List, Optional, Sequence

import jax
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


def _page_key(seq_id: int, name: str, idx: int) -> int:
    return splitmix64(hash((seq_id, name, idx)) & 0x7FFFFFFFFFFFFFFF) | 1


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
                         "restores": 0}

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
        self.store.delete(_page_key(seq_id, name, idx))

    # ------------------------------------------------- cache snapshot/restore
    def snapshot_cache(self, seq_id: int, cache) -> int:
        """Persist a whole decode cache pytree as numbered pages — one batched
        multi_write (2 doorbells per shard), not one write per leaf.  A leaf
        larger than one record is split into record-sized pages, since a
        record never spans a log segment.  Counts the bytes of each kind of
        leaf (``leaf_kind``) besides the total."""
        with obs.span("pages.snapshot", seq_id=seq_id) as sp:
            page = self.store.max_value_bytes
            items = []
            by_kind = {"state": 0, "kv": 0}
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
                kind = leaf_kind(path)
                with obs.span("pages.to_host", kind=kind) as th:
                    host = np.asarray(leaf)
                    th.set(nbytes=host.nbytes)
                with obs.span("pages.encode", nbytes=host.nbytes, kind=kind):
                    blob = leaf_to_bytes(host)
                    name = jax.tree_util.keystr(path)
                    items += [(_page_key(seq_id, name, i),
                               blob[off : off + page])
                              for i, off in enumerate(range(0, len(blob),
                                                            page))]
                by_kind[kind] += len(blob)
            self.store.multi_write(items)
            nbytes = sum(len(v) for _, v in items)
            self.counters["snapshots"] += 1
            self.counters["snapshot_bytes"] += nbytes
            self.counters["snapshot_state_bytes"] += by_kind["state"]
            self.counters["snapshot_kv_bytes"] += by_kind["kv"]
            sp.set(nbytes=nbytes)
            return len(items)

    def restore_cache(self, seq_id: int, template):
        """The cache pytree last snapshotted for ``seq_id``, as host arrays
        shaped like ``template`` (arrays or ShapeDtypeStructs), or None.  One
        batched multi_read fetches every page of every leaf."""
        with obs.span("pages.restore", seq_id=seq_id) as sp:
            page = self.store.max_value_bytes
            leaves = jax.tree_util.tree_flatten_with_path(template)[0]
            n_pages = [-(-encoded_size(leaf.shape, leaf.dtype) // page)
                       for _, leaf in leaves]
            raws = self.store.multi_read(
                [_page_key(seq_id, jax.tree_util.keystr(path), i)
                 for (path, _), n in zip(leaves, n_pages) for i in range(n)])
            if any(raw is None for raw in raws):
                return None
            out, at = [], 0
            for (_, leaf), n in zip(leaves, n_pages):
                with obs.span("pages.decode") as dec:
                    blob = b"".join(raws[at : at + n])
                    at += n
                    out.append(leaf_from_bytes(blob).astype(leaf.dtype,
                                                            copy=False))
                    dec.set(nbytes=len(blob))
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
        """Simulate a serving host losing a page shard's NVM."""
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
