"""Logical-axis → mesh sharding rules (path-regex based, MaxText-style).

Mesh axes: ('pod', 'data', 'model') multi-pod, ('data', 'model') single-pod.
  pod    — pure DP: gradients cross the slow inter-pod links once per step
  data   — FSDP: the 'embed'-like dimension of every weight shards here, so a
           mixtral-8x22b train state (141B × 12B/param) fits 256×16 GB chips;
           weights are all-gathered per layer inside the scan (compute/comm
           overlap via the XLA latency-hiding scheduler)
  model  — TP: heads / d_ff / vocab / d_inner; EP when n_experts divides it

Batch shards over (pod, data); decode caches shard batch — or, when batch
can't shard (long_500k has B=1), the cache SEQUENCE dimension shards over
'data' (sequence parallelism for the KV pages).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    mesh: Mesh

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.mesh.axis_names

    @property
    def data_axes(self) -> Tuple[str, ...]:
        return ("pod", "data") if self.multi_pod else ("data",)

    @property
    def model_size(self) -> int:
        return self.mesh.shape["model"]

    @property
    def data_size(self) -> int:
        d = self.mesh.shape["data"]
        return d * (self.mesh.shape["pod"] if self.multi_pod else 1)

    @property
    def fsdp_size(self) -> int:
        return self.mesh.shape["data"]


# --------------------------------------------------------------- activations
# Batch-dim sharding constraints for activations (MaxText-style): GSPMD can
# lose the batch sharding through gathers (embedding lookups), silently
# replicating (B,S,d) activations across the data axis.  Models call
# constrain_batch() at block boundaries; it is a no-op unless the launcher
# declared the activation batch axes for the current mesh.
_ACTIVATION_BATCH_AXES: Optional[Tuple[str, ...]] = None
_ACTIVATION_SEQ_AXIS: Optional[Tuple[str, int]] = None  # (axis name, size)


def set_activation_batch_axes(axes: Optional[Tuple[str, ...]]) -> None:
    global _ACTIVATION_BATCH_AXES
    _ACTIVATION_BATCH_AXES = tuple(axes) if axes else None


def set_activation_seq_axis(axis: Optional[str], size: int = 0) -> None:
    """Megatron-style sequence parallelism for the residual stream: (B,S,d)
    activations at block boundaries additionally shard S over the TP axis, so
    the per-layer scan carry saved for backward is 1/tp_size the size.  GSPMD
    re-gathers at the qkv/mlp projections (all-gather) and scatters after
    (reduce-scatter) — same wire bytes as the all-reduce it replaces."""
    global _ACTIVATION_SEQ_AXIS
    _ACTIVATION_SEQ_AXIS = (axis, size) if axis else None


def constrain_batch_only(x):
    """Pin dim0 to (pod,data) and force every other dim replicated.  Used at
    the MoE expert-FFN boundary: the dispatched activations must NOT carry the
    sequence's 'model' sharding, or it conflicts with the expert weights'
    TP-sharded d_ff and GSPMD falls back to fully replicating the experts."""
    if _ACTIVATION_BATCH_AXES is None or x.ndim < 2:
        return x
    spec = P(_ACTIVATION_BATCH_AXES, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_batch(x):
    """Pin dim0 of an activation to (pod, data); optionally dim1 to the TP
    axis (sequence parallelism) when divisible."""
    if _ACTIVATION_BATCH_AXES is None or x.ndim < 2:
        return x
    rest = [None] * (x.ndim - 1)
    if (_ACTIVATION_SEQ_AXIS is not None and x.ndim == 3
            and x.shape[1] % max(_ACTIVATION_SEQ_AXIS[1], 1) == 0
            and x.shape[1] >= _ACTIVATION_SEQ_AXIS[1]):
        rest[0] = _ACTIVATION_SEQ_AXIS[0]
    spec = P(_ACTIVATION_BATCH_AXES, *rest)
    return jax.lax.with_sharding_constraint(x, spec)


# Sharding policy: 'tp' (default — TP over 'model', FSDP over 'data') or
# 'dp' (pure data parallel + FSDP over BOTH axes: right for small models whose
# TP collectives would dwarf their compute — see EXPERIMENTS.md §Perf).
_POLICY = "tp"


def set_policy(policy: str) -> None:
    global _POLICY
    assert policy in ("tp", "dp", "serve")
    _POLICY = policy


def get_policy() -> str:
    return _POLICY


# (regex, base_rank, trailing spec) — leading stacked-layer dims are padded
# with None.  Trailing spec axes: F = fsdp('data'), T = tp('model').
F, T = "data", "model"
_RULES = [
    (r"embed/table$",        2, (T, F)),
    (r"embed/unembed$",      2, (F, T)),
    (r"dec_pos$",            2, (None, F)),
    (r"attn/w[qkv]$",        2, (F, T)),
    (r"attn/wo$",            2, (T, F)),
    (r"mlp/w[gi]$",          2, (F, T)),
    (r"mlp/wo$",             2, (T, F)),
    (r"moe/router$",         2, (F, None)),
    (r"moe/w[gi]$",          3, "MOE_IN"),
    (r"moe/wo$",             3, "MOE_OUT"),
    (r"ssm/in_proj$",        2, (F, T)),
    (r"ssm/out_proj$",       2, (T, F)),
    (r"ssm/conv_w$",         2, (None, T)),
    (r"ssm/conv_b$",         1, (T,)),
    (r"ssm/(A_log|D|dt_bias)$", 1, (None,)),
    (r"ssm/gate_norm$",      1, (T,)),
    (r"tm/w[rkvg]$",         2, (F, T)),
    (r"tm/wo$",              2, (T, F)),
    (r"tm/w_lora_a$",        2, (F, None)),
    (r"tm/w_lora_b$",        2, (None, T)),
    (r"tm/(mu|w0|u|ln)$",    0, "REPL"),
    (r"cm/w[rk]$",           2, (F, T)),
    (r"cm/wv$",              2, (T, F)),
    (r"cm/mu$",              0, "REPL"),
    (r"(ln1|ln2|ln_x|ln_in|ln|final_norm|enc_norm|gate_norm)(/scale)?$", 0, "REPL"),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def spec_for_param(path: str, shape: Tuple[int, ...], info: MeshInfo,
                   n_experts: int = 0) -> P:
    for regex, base_rank, trailing in _RULES:
        if re.search(regex, path):
            if trailing == "REPL":
                return P()
            if trailing == "MOE_IN":      # (E, d, f)
                if n_experts and n_experts % info.model_size == 0:
                    trailing = (T, F, None)       # true EP
                else:
                    trailing = (None, F, T)       # TP-MoE
            elif trailing == "MOE_OUT":   # (E, f, d)
                if n_experts and n_experts % info.model_size == 0:
                    trailing = (T, None, F)
                else:
                    trailing = (None, T, F)
            lead = len(shape) - len(trailing)
            spec = (None,) * lead + tuple(trailing)
            if _POLICY == "dp":
                # fold TP away; FSDP over the merged (data, model) axes
                spec = tuple(("data", "model") if ax == F else
                             (None if ax == T else ax) for ax in spec)
            elif _POLICY == "serve":
                # replicate params over 'data' (no per-layer FSDP gathers on
                # the decode path); TP over 'model' carries the weights
                spec = tuple(None if ax == F else ax for ax in spec)
            # drop shardings that don't divide (robustness for reduced configs)
            fixed = []
            for dim, ax in zip(shape, spec):
                if ax == ("data", "model"):
                    size = info.fsdp_size * info.model_size
                elif ax in (F, T):
                    size = {F: info.fsdp_size, T: info.model_size}.get(ax, 1)
                else:
                    size = 1
                fixed.append(ax if ax and dim % size == 0 and dim >= size else None)
            return P(*fixed)
    return P()  # default: replicate


def param_specs(params, info: MeshInfo, n_experts: int = 0):
    """Pytree of PartitionSpec matching `params` (arrays or ShapeDtypeStructs)."""
    def one(path, leaf):
        return spec_for_param(_path_str(path), leaf.shape, info, n_experts)
    return jax.tree_util.tree_map_with_path(one, params)


def batch_axes(info: MeshInfo):
    if _POLICY == "dp":
        return info.data_axes + ("model",)
    return info.data_axes


def batch_spec(batch, info: MeshInfo):
    """tokens/frames/patches: shard the leading batch dim over (pod, data)
    (+ 'model' under the dp policy)."""
    da = batch_axes(info)
    dsz = info.data_size * (info.model_size if _POLICY == "dp" else 1)

    def one(leaf):
        b = leaf.shape[0]
        if b % dsz == 0:
            return P(da, *([None] * (len(leaf.shape) - 1)))
        if b % info.data_size == 0:
            return P(info.data_axes, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))
    return jax.tree.map(one, batch)


def cache_specs(cache, info: MeshInfo, *, batch_size: int):
    """Decode caches: shard batch over (pod,data) when divisible; otherwise
    (long_500k, B=1) shard the big sequence/capacity dimension over 'data'
    (sequence parallelism), heads over 'model'."""
    da = info.data_axes
    batch_ok = batch_size % info.data_size == 0

    def one(path, leaf):
        shape = leaf.shape
        name = _path_str(path)
        if leaf.dtype.name.startswith("int") and len(shape) <= 2:
            # kv_pos (L, C): shard C over data in seq-parallel mode
            if not batch_ok and len(shape) == 2 and shape[1] % info.fsdp_size == 0:
                return P(None, F)
            return P(*([None] * len(shape)))
        if len(shape) == 0:
            return P()
        # find the batch dim: first dim equal to batch_size after leading stacks
        spec = [None] * len(shape)
        bdims = [i for i, s in enumerate(shape) if s == batch_size]
        if batch_ok and bdims:
            spec[bdims[0]] = da
            # shard heads/channels over model: prefer the second-to-last dim
            # (KV heads for attention caches, channels for states) — sharding
            # the capacity/sequence dim over 'model' would split the softmax
            candidates = [len(shape) - 2] + list(range(bdims[0] + 1, len(shape)))
            for i in candidates:
                if i <= bdims[0]:
                    continue
                if shape[i] % info.model_size == 0 and shape[i] >= info.model_size:
                    spec[i] = T
                    break
        elif not batch_ok:
            # sequence parallelism: shard the largest dim over data
            big = max(range(len(shape)), key=lambda i: shape[i])
            if shape[big] % info.fsdp_size == 0 and shape[big] > 1:
                spec[big] = F
            for i in range(len(shape)):
                if i != big and shape[i] % info.model_size == 0 and shape[i] >= info.model_size:
                    spec[i] = T
                    break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache)
