"""Logical-axis → mesh sharding rules (path-regex based, MaxText-style).

Mesh axes: ('pod', 'data', 'model') multi-pod, ('data', 'model') single-pod.
  pod    — pure DP: gradients cross the slow inter-pod links once per step
  data   — FSDP: the 'embed'-like dimension of every weight shards here;
           weights are all-gathered per layer inside the scan (compute/comm
           overlap via the XLA latency-hiding scheduler)
  model  — TP: heads / d_ff / vocab / d_inner

Batch shards over (pod, data); decode caches shard batch — or, when batch
can't shard (B=1), the cache SEQUENCE dimension shards over 'data'
(sequence parallelism for the KV pages).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P


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


# (regex, trailing spec) — leading stacked-layer dims are padded with None.
# Trailing spec axes: F = fsdp('data'), T = tp('model'); () replicates.
F, T = "data", "model"
_RULES = [
    (r"embed/table$",        (T, F)),
    (r"embed/unembed$",      (F, T)),
    (r"attn/w[qkv]$",        (F, T)),
    (r"attn/wo$",            (T, F)),
    (r"mlp/w[gi]$",          (F, T)),
    (r"mlp/wo$",             (T, F)),
    (r"ssm/in_proj$",        (F, T)),
    (r"ssm/out_proj$",       (T, F)),
    (r"ssm/conv_w$",         (None, T)),
    (r"ssm/conv_b$",         (T,)),
    (r"ssm/(A_log|D|dt_bias)$", (None,)),
    (r"ssm/gate_norm$",      (T,)),
    (r"tm/w[rkvg]$",         (F, T)),
    (r"tm/wo$",              (T, F)),
    (r"tm/w_lora_a$",        (F, None)),
    (r"tm/w_lora_b$",        (None, T)),
    (r"tm/(mu|w0|u|ln)$",    ()),
    (r"cm/w[rk]$",           (F, T)),
    (r"cm/wv$",              (T, F)),
    (r"cm/mu$",              ()),
    (r"(ln1|ln2|ln_in|ln|final_norm)(/scale)?$", ()),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def spec_for_param(path: str, shape: Tuple[int, ...], info: MeshInfo) -> P:
    for regex, trailing in _RULES:
        if re.search(regex, path):
            if not trailing:
                return P()
            lead = len(shape) - len(trailing)
            spec = (None,) * lead + tuple(trailing)
            # drop shardings that don't divide (robustness for reduced configs)
            size = {F: info.fsdp_size, T: info.model_size}
            return P(*(ax if ax and dim % size[ax] == 0 and dim >= size[ax]
                       else None for dim, ax in zip(shape, spec)))
    return P()  # default: replicate


def param_specs(params, info: MeshInfo):
    """Pytree of PartitionSpec matching `params` (arrays or ShapeDtypeStructs)."""
    def one(path, leaf):
        return spec_for_param(_path_str(path), leaf.shape, info)
    return jax.tree_util.tree_map_with_path(one, params)


def batch_spec(batch, info: MeshInfo):
    """tokens: shard the leading batch dim over (pod, data)."""
    def one(leaf):
        if leaf.shape[0] % info.data_size == 0:
            return P(info.data_axes, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))
    return jax.tree.map(one, batch)


def cache_specs(cache, info: MeshInfo, *, batch_size: int):
    """Decode caches: shard batch over (pod,data) when divisible; otherwise
    (B=1) shard the big sequence/capacity dimension over 'data' (sequence
    parallelism), heads over 'model'."""
    da = info.data_axes
    batch_ok = batch_size % info.data_size == 0

    def one(path, leaf):
        shape = leaf.shape
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
