"""Erda-protocol checkpoint manager — the paper's technique as the fault-
tolerance substrate of the training framework (DESIGN.md §2).

Mapping:
  * every train-state leaf (optionally split into sub-shards) is an Erda
    OBJECT: one one-sided write, CRC32 inside, no redo-log double write;
  * the checkpoint MANIFEST is one object updated per step — publishing it is
    the server's single 8-byte atomic flip, so a checkpoint becomes visible
    atomically, and the previous checkpoint's manifest stays reachable as the
    OLD version (out-of-place log ⇒ implicit undo);
  * a writer that dies mid-shard leaves a torn object: restore detects it via
    CRC (the client read path), falls back shard-wise or manifest-wise to the
    last consistent version, and repairs server metadata — no coordinator, no
    fsync barriers, no write amplification (Table 1's ≈50 % saving applies to
    every checkpoint byte);
  * stragglers: a slow writer simply hasn't flipped its entry — readers keep
    using the old version (no blocking).

This is deliberately the same ErdaServer/ErdaClient code path the KV benches
use — the checkpoint layer adds only keying, manifests, and pytree assembly.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.checkpoint.serialization import leaf_from_bytes, leaf_to_bytes
from repro.core import DataLossError, ErdaStore
from repro.core.hashtable import splitmix64


def _leaf_key(tag: str, step: int, path: str, shard: int) -> int:
    h = splitmix64(hash((tag, step, path, shard)) & 0x7FFFFFFFFFFFFFFF)
    return h | 1  # keys must be non-zero


MANIFEST_KEY = 0x3A5F00D  # fixed key: its 8-byte atomic flip IS the commit


class WriterCrash(RuntimeError):
    """The checkpoint writer died mid-save (injected by ``fail_after_shards``)."""


class ErdaCheckpointManager:
    def __init__(self, store: Optional[ErdaStore] = None, *, tag: str = "ckpt",
                 shard_bytes: int = 4 << 20):
        from repro.core import ServerConfig
        self.store = store or ErdaStore(ServerConfig(
            device_size=1 << 30, table_capacity=1 << 15,
            n_heads=8, region_size=32 << 20, segment_size=8 << 20))
        self.tag = tag
        self.shard_bytes = shard_bytes

    # ------------------------------------------------------------------ save
    def save(self, step: int, state, *, fail_after_shards: Optional[int] = None):
        """Write all shards, then commit the manifest (one atomic flip).
        `fail_after_shards` injects a mid-checkpoint crash for tests."""
        leaves = jax.tree_util.tree_flatten_with_path(state)[0]
        entries = []
        written = 0
        for path, leaf in leaves:
            pstr = jax.tree_util.keystr(path)
            blob = leaf_to_bytes(leaf)
            shards = [blob[i : i + self.shard_bytes]
                      for i in range(0, len(blob), self.shard_bytes)] or [b""]
            for si, sh in enumerate(shards):
                if fail_after_shards is not None and written >= fail_after_shards:
                    raise WriterCrash("injected checkpoint-writer crash")
                self.store.write(_leaf_key(self.tag, step, pstr, si), sh)
                written += 1
            entries.append({"path": pstr, "shards": len(shards)})
        manifest = json.dumps({"step": step, "entries": entries}).encode()
        # THE commit point: one Erda update = one 8-byte atomic flip
        self.store.write(MANIFEST_KEY, manifest)
        return written

    # --------------------------------------------------------------- restore
    def _try_restore(self, manifest: Dict, treedef_state) -> Any:
        leaves = jax.tree_util.tree_flatten_with_path(treedef_state)[0]
        by_path = {jax.tree_util.keystr(p): l for p, l in leaves}
        out = {}
        for e in manifest["entries"]:
            blob = b""
            for si in range(e["shards"]):
                v = self.store.read(_leaf_key(self.tag, manifest["step"], e["path"], si))
                if v is None:
                    raise DataLossError(f"missing shard {e['path']}#{si}")
                blob += v
            out[e["path"]] = leaf_from_bytes(blob)
        flat = [out[jax.tree_util.keystr(p)] for p, _ in leaves]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(treedef_state), flat)

    def restore(self, template) -> Tuple[Optional[int], Any]:
        """Returns (step, state) of the newest CONSISTENT checkpoint.
        The Erda client transparently falls back to the old manifest version if
        the new one is torn; torn shards of the new step push the restore back
        to the previous committed step."""
        raw = self.store.read(MANIFEST_KEY)
        if raw is None:
            return None, None
        manifest = json.loads(bytes(raw).decode())
        try:
            return manifest["step"], self._try_restore(manifest, template)
        except DataLossError:
            pass
        # shards of the latest step torn → previous manifest version
        entry = self.store.server.table.lookup(MANIFEST_KEY)
        from repro.core import layout
        _tag, _new, off_old = layout.unpack_word(entry.word)
        if off_old == layout.NULL_OFF:
            return None, None
        rec = layout.parse_record(self.store.dev.mem, off_old)
        if not rec.ok:
            return None, None
        manifest = json.loads(rec.value.decode())
        return manifest["step"], self._try_restore(manifest, template)

    # ----------------------------------------------------- failure injection
    def crash_recover(self):
        """Simulate server restart: recovery scan + metadata repair (§4.2)."""
        return self.store.server.recover()
