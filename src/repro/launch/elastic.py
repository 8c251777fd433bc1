"""Elastic scaling + straggler policy.

Erda checkpoints are stored shape-canonical (full logical arrays, sharded into
fixed-size log objects), so restoring onto a DIFFERENT mesh is just
device_put with the new sharding — demonstrated by ``reshard_restore`` and
tested in tests/test_checkpoint.py.  Straggler policy is inherited from the
protocol itself: a writer that never commits simply never flips the manifest
word; readers keep the previous version (no barrier, no timeout coordination).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.checkpoint import ErdaCheckpointManager
from repro.sharding import MeshInfo, param_specs


def reshard_restore(mgr: ErdaCheckpointManager, template, mesh):
    """Restore the newest consistent checkpoint onto `mesh` (any size)."""
    step, state = mgr.restore(template)
    if step is None:
        return None, None
    info = MeshInfo(mesh)
    pspec = param_specs(state["params"], info)

    def put(leaf, spec):
        return jax.device_put(jnp.asarray(leaf),
                              jax.sharding.NamedSharding(mesh, spec))

    params = jax.tree.map(put, state["params"], pspec)
    opt = {
        "m": jax.tree.map(put, state["opt"]["m"], pspec),
        "v": jax.tree.map(put, state["opt"]["v"], pspec),
        "step": jnp.asarray(state["opt"]["step"]),
    }
    return step, {"params": params, "opt": opt}
