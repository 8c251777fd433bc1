"""Sharding rules: spec shapes, divisibility fallbacks, cache specs (batch vs
sequence parallel)."""
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
    # a vocab of 51865 is not divisible by 16 → replicate that dim
    assert spec_for_param("embed/table", (51865, 768), i) == P(None, "data")


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


@pytest.mark.parametrize("arch", ["olmo_1b", "rwkv6_1p6b", "zamba2_7b"])
def test_every_arch_param_tree_has_specs(arch):
    i = info(pod=2)
    params = get_model(get_config(arch)).init_abstract(max_seq=512)
    specs = param_specs(params, i)
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
