"""Sharding rules: spec shapes, divisibility fallbacks, EP-vs-TP MoE choice,
cache specs (batch vs sequence parallel)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import get_model
from repro.sharding import MeshInfo, batch_spec, cache_specs, param_specs
from repro.sharding.rules import spec_for_param


class FakeMesh:
    """Just enough of a Mesh for MeshInfo (no devices needed)."""
    def __init__(self, shape_map):
        self.axis_names = tuple(shape_map)
        self.shape = dict(shape_map)


def info(pod=0, data=16, model=16):
    m = FakeMesh({"pod": pod, "data": data, "model": model} if pod
                 else {"data": data, "model": model})
    return MeshInfo(m)  # type: ignore


def test_attention_param_specs():
    i = info()
    assert spec_for_param("layers/attn/wq", (40, 5120, 4096), i) == P(None, "data", "model")
    assert spec_for_param("layers/attn/wo", (40, 4096, 5120), i) == P(None, "model", "data")
    assert spec_for_param("layers/mlp/wg", (40, 5120, 14336), i) == P(None, "data", "model")
    assert spec_for_param("embed/table", (131072, 5120), i) == P("model", "data")


def test_norms_replicated():
    i = info()
    assert spec_for_param("layers/ln1/scale", (40, 5120), i) == P()
    assert spec_for_param("final_norm/scale", (5120,), i) == P()


def test_non_divisible_drops_axis():
    i = info()
    # whisper vocab 51865 is not divisible by 16 → replicate that dim
    assert spec_for_param("embed/table", (51865, 768), i) == P(None, "data")


def test_moe_tp_when_experts_not_divisible():
    i = info()
    # mixtral: 8 experts, model=16 → TP-MoE (f over model, d over data)
    s = spec_for_param("layers/moe/wg", (56, 8, 6144, 16384), i, n_experts=8)
    assert s == P(None, None, "data", "model")
    s = spec_for_param("layers/moe/wo", (56, 8, 16384, 6144), i, n_experts=8)
    assert s == P(None, None, "model", "data")


def test_moe_ep_when_divisible():
    i = info(model=8)
    # 8 experts on an 8-wide model axis → true EP (experts sharded)
    s = spec_for_param("layers/moe/wg", (56, 8, 6144, 16384), i, n_experts=8)
    assert s == P(None, "model", "data", None)


def test_local_global_stacked_lead_dims():
    i = info()
    # gemma3 locals are (G, 5, d, qdim): two leading stack dims padded None
    s = spec_for_param("local_layers/attn/wq", (8, 5, 3840, 4096), i)
    assert s == P(None, None, "data", "model")


def test_batch_spec_multi_pod():
    i = info(pod=2)
    spec = batch_spec({"tokens": jax.ShapeDtypeStruct((256, 4096), np.int32)}, i)
    assert spec["tokens"] == P(("pod", "data"), None)


def test_batch_spec_indivisible_replicates():
    i = info(pod=2)
    spec = batch_spec({"tokens": jax.ShapeDtypeStruct((1, 64), np.int32)}, i)
    assert spec["tokens"] == P(None, None)


def test_cache_spec_batch_sharded():
    i = info()
    model = get_model(get_config("olmo_1b"))
    cache = jax.eval_shape(lambda: model.init_cache(128, 1024))
    spec = cache_specs(cache, i, batch_size=128)
    k = spec["full"]["k"]   # (L, B, C, KV, hd)
    assert k[1] in ("data", ("data",)) and k[3] == "model"


def test_cache_spec_seq_parallel_for_batch1():
    i = info()
    model = get_model(get_config("rwkv6_1p6b"))
    cache = jax.eval_shape(lambda: model.init_cache(1, 2048))
    spec = cache_specs(cache, i, batch_size=1)
    # some big dim must be sharded over data, none over the batch dim
    leaves = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, P))
    assert any("data" in str(s) for s in leaves)


def test_every_arch_param_tree_has_specs():
    i = info(pod=2)
    for arch in ("olmo_1b", "mixtral_8x22b", "zamba2_1p2b", "zamba2_7b", "rwkv6_1p6b",
                 "whisper_small", "gemma3_27b"):
        cfg = get_config(arch)
        model = get_model(cfg)
        params = model.init_abstract(max_seq=512)
        specs = param_specs(params, i, cfg.n_experts)
        n_params = len(jax.tree.leaves(params))
        n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_params == n_specs
        # every sharded dim must divide
        flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for (path, leaf), spec in zip(flat_p, flat_s):
            for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 9):
                if ax is None:
                    continue
                size = {"data": 16, "model": 16}.get(ax if isinstance(ax, str) else ax[0], 1)
                assert dim % size == 0, f"{arch} {path} {leaf.shape} {spec}"
