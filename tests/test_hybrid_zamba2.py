"""Zamba2's hybrid block in the program (``models/hybrid.py``,
``models/layers/ssm.py``) against the plain float32 reference the chip
benchmark checks it with (``benchmarks/chip/families/zamba2.py``), its layer
order, its two-group Mamba2, its parameter count, and the page store's split
of a snapshot into rewritten state and append-only KV."""
import dataclasses
import functools
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ServerConfig, make_store
from repro.models import get_model
from repro.models import hybrid as H
from repro.models.layers import ssm as S
from repro.serving.kv_store import ErdaKVPageStore, leaf_kind

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))
from families import zamba2 as ref  # noqa: E402

#: hybrid layers 1, 3 and 4 of 6: calls 0, 1, 2 use blocks 0, 1, 0, and
#: layers 3 and 4 are hybrid back to back
IDS = (1, 3, 4)


def tiny(dtype="float32", **kw):
    """Zamba2-7B's block at smoke widths: 2 shared blocks, 2 B/C groups of 2
    heads, conv bias, adapters, gated erf-GELU MLP."""
    cfg = dataclasses.replace(
        get_config("zamba2_7b"), n_layers=6, hybrid_layer_ids=IDS,
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
        vocab_size=256, ssm_state=8, ssm_head_dim=32, ssm_chunk=8,
        adapter_rank=8, remat="none", dtype=dtype, **kw)
    assert (cfg.ssm_heads, cfg.ssm_groups, cfg.n_mem_blocks) == (4, 2, 2)
    return cfg


def model_dict(cfg) -> dict:
    """The reference's view of a configuration (a configuration file's
    ``model`` group)."""
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "norm", "mlp_kind", "act", "rope_theta",
            "tie_embeddings", "dtype", "ssm_state", "ssm_conv", "ssm_expand",
            "ssm_head_dim", "ssm_groups", "ssm_conv_bias", "ssm_chunk",
            "hybrid_layer_ids", "n_mem_blocks", "adapter_rank")
    return {k: getattr(cfg, k) for k in keys}


def random_params(cfg, seed=0):
    """Every leaf random, the decay and step parameters in Mamba2's ranges,
    so that no part of the block is an identity."""
    shapes = get_model(cfg).init_abstract()
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    rng = np.random.default_rng(seed)
    out = []
    for path, s in leaves:
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            a = rng.uniform(0.0, 1.5, s.shape)
        elif "dt_bias" in name:
            a = rng.uniform(-3.0, -1.0, s.shape)
        elif len(s.shape) and ("scale" in name or "gate_norm" in name
                               or "'D'" in name):
            a = 1.0 + 0.2 * rng.standard_normal(s.shape)
        elif len(s.shape) >= 2 and "table" not in name:
            a = rng.standard_normal(s.shape) / math.sqrt(s.shape[-2])
        else:
            a = 0.3 * rng.standard_normal(s.shape)
        out.append(jnp.asarray(a, s.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


@functools.lru_cache(maxsize=None)
def compiled(cfg):
    """The program's jitted prefill and decode step, once per config."""
    model = get_model(cfg)
    return jax.jit(model.prefill), jax.jit(model.decode_step)


def served_logits(cfg, params, prompt, n_decode):
    """Prefill logits of the prompt's last position, then ``n_decode`` greedy
    decode steps through the cache; returns (logits (B, 1+n, V), tokens)."""
    prefill, step = compiled(cfg)
    logits, cache = prefill(params, {"tokens": prompt})
    seq, tokens = [logits[:, 0]], prompt
    for _ in range(n_decode):
        tok = jnp.argmax(seq[-1], -1).astype(jnp.int32)[:, None]
        tokens = jnp.concatenate([tokens, tok], axis=1)
        logits, cache = step(params, cache, tok)
        seq.append(logits[:, 0])
    return np.stack([np.asarray(s, np.float32) for s in seq], 1), tokens


def test_prefill_and_decode_match_reference():
    """Prefill of 16 tokens and 4 decode steps, against the reference's full
    forward over the 20 tokens.  The program runs in float32 here, so what is
    left is summation order and the chunked SSD against the stepped
    recurrence: under 1e-4 of logits of order 1.  TOL is 2e-3; the program
    in bfloat16 misses it by far (its rounding alone moves logits ~1e-2)."""
    TOL = 2e-3
    cfg = tiny()
    params = random_params(cfg)
    prompt = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 16)), jnp.int32)
    got, tokens = served_logits(cfg, params, prompt, 4)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(params, tokens, model_dict(cfg)))[:, 15:]
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    cfg16 = tiny("bfloat16")
    model16 = get_model(cfg16)
    p16 = jax.tree.map(lambda a, s: a.astype(s.dtype), params,
                       model16.init_abstract())
    low, _ = compiled(cfg16)[0](p16, {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        full = np.asarray(ref.logits(params, tokens, model_dict(cfg)))
    assert np.abs(np.asarray(low[:, 0], np.float32) - full[:, -1]).max() > TOL


def _layer_states(cfg, params, prompt):
    _, cache = compiled(cfg)[0](params, {"tokens": prompt})
    return [np.asarray(st["h"]) for st in cache["mamba"]]


@pytest.mark.parametrize("part,index,first", [
    ("mamba", 2, 2),    # a plain layer moves itself and what follows
    ("shared", 0, 1),   # block 0: calls 0 and 2, first at layer 1
    ("shared", 1, 3),   # block 1: call 1 only, at layer 3
    ("adapter", 0, 1),  # call j's own adapter acts at the j-th hybrid layer
    ("adapter", 2, 4),
    ("linear", 1, 3),   # call j's own output projection
])
def test_block_alternation_and_calls_follow_hybrid_ids(part, index, first):
    """Perturbing one block, adapter or projection changes the Mamba2 states
    from the first layer that uses it on, and none before: hybrid layer ids
    (1, 3, 4) are calls 0, 1, 2 of blocks 0, 1, 0, each with its own adapter
    and projection."""
    cfg = tiny()
    assert H._calls(cfg) == {1: 0, 3: 1, 4: 2}
    params = random_params(cfg)
    prompt = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    where = {"mamba": ("mamba", index, "ssm", "in_proj"),
             "shared": ("shared", index, "mlp", "wo"),
             "adapter": ("calls", index, "adapter", "b"),
             "linear": ("calls", index, "linear")}[part]
    changed = jax.tree.map(lambda a: a, params)
    node = changed
    for k in where[:-1]:
        node = node[k]
    node[where[-1]] = node[where[-1]] * 1.5
    a = _layer_states(cfg, params, prompt)
    b = _layer_states(cfg, changed, prompt)
    moved = [not np.allclose(a[i], b[i], rtol=0, atol=1e-6)
             for i in range(cfg.n_layers)]
    assert moved == [i >= first for i in range(cfg.n_layers)]


def _stepped_groups(x, B_in, C_in, dt, A_):
    """The recurrence token by token; head n reads group n // (nh / G)."""
    Bsz, T, nh, hp = x.shape
    G = B_in.shape[2]
    h = np.zeros((Bsz, nh, hp, B_in.shape[-1]))
    ys = np.zeros(x.shape)
    for t in range(T):
        Bh = np.repeat(B_in[:, t], nh // G, axis=1)
        Ch = np.repeat(C_in[:, t], nh // G, axis=1)
        h = (h * np.exp(dt[:, t] * A_)[..., None, None]
             + (dt[:, t][..., None] * x[:, t])[..., None] * Bh[:, :, None, :])
        ys[:, t] = np.einsum("bhpn,bhn->bhp", h, Ch)
    return ys, h


@pytest.mark.parametrize("T,chunk", [(24, 8), (20, 8), (16, 16)])
def test_two_group_chunked_ssd_matches_stepped_recurrence(T, chunk):
    cfg = dataclasses.replace(tiny(), ssm_chunk=chunk)
    rng = np.random.default_rng(T)
    Bsz, nh, hp, G, N = 2, 4, 8, 2, 6
    x = rng.standard_normal((Bsz, T, nh, hp))
    B_in = rng.standard_normal((Bsz, T, G, N))
    C_in = rng.standard_normal((Bsz, T, G, N))
    dt = rng.uniform(0.01, 0.5, (Bsz, T, nh))
    A_ = -rng.uniform(0.5, 2.0, nh)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, h = S.ssm_chunked(cfg, f32(x), f32(B_in), f32(C_in), f32(dt), f32(A_))
    want_y, want_h = _stepped_groups(x, B_in, C_in, dt, A_)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-4, atol=1e-4)
    # one group for all heads is another answer: the groups are read
    one, _ = _stepped_groups(x, B_in[:, :, :1], C_in[:, :, :1], dt, A_)
    assert np.abs(one - want_y).max() > 0.1


def test_grouped_gated_rms_norm_by_hand():
    rng = np.random.default_rng(5)
    y = rng.standard_normal((2, 3, 8))
    z = rng.standard_normal((2, 3, 8))
    w = rng.uniform(0.5, 1.5, 8)
    g = y * z / (1 + np.exp(-z))
    want = np.empty_like(g)
    for lo in (0, 4):  # two groups of four channels, each normalised alone
        part = g[..., lo:lo + 4]
        want[..., lo:lo + 4] = part / np.sqrt((part ** 2).mean(-1, keepdims=True)
                                              + 1e-6)
    got = S.gated_rms_norm(jnp.asarray(y, jnp.float32),
                           jnp.asarray(z, jnp.float32), jnp.asarray(w), 2)
    np.testing.assert_allclose(np.asarray(got), want * w, rtol=1e-5, atol=1e-6)


def _tree_size(cfg) -> int:
    return sum(math.prod(s.shape)
               for s in jax.tree.leaves(get_model(cfg).init_abstract()))


@pytest.mark.parametrize("name,n_layers", [("zamba2_7b", 12), ("zamba2_7b", None),
                                           ("tiny", None)])
def test_param_count_is_the_program_tree(name, n_layers):
    cfg = tiny() if name == "tiny" else get_config(name)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    assert cfg.param_count() == _tree_size(cfg)
    if cfg.act == "gelu_exact":  # the reference's Zamba2-7B block
        assert ref.param_count(model_dict(cfg)) == cfg.param_count()
    if (name, n_layers) == ("zamba2_7b", 12):
        mamba = (3584 * (7168 + 7424 + 112) + 4 * 7424 + 7424 + 3 * 112
                 + 7168 + 7168 * 3584 + 3584)
        shared = 3 * 7168 * 7168 + 7168 * 3584 + 3 * 3584 * 14336 + 7168 + 3584
        call = 3584 * 3584 + 128 * (3584 + 2 * 14336)
        hand = 12 * mamba + 2 * shared + 2 * call + 32000 * 3584 + 3584
        assert (mamba, shared, call) == (78_437_456, 333_982_208, 16_973_824)
        assert cfg.param_count() == hand == 1_757_853_120


SMALL = ServerConfig(device_size=64 << 20, table_capacity=1 << 12, n_heads=2,
                     region_size=4 << 20, segment_size=1 << 20)


@pytest.mark.parametrize("arch,kv_leaves", [
    ("olmo_1b", 3), ("rwkv6_1p6b", 0), ("zamba2_7b", 6)])
def test_snapshot_counts_state_and_kv_bytes(arch, kv_leaves):
    """Each snapshot's bytes split into rewritten state and append-only KV,
    and the two add up to ``snapshot_bytes``; K, V and their positions count
    as KV, every other leaf (SSM and RWKV states, conv windows, the decode
    position) as state."""
    cfg = get_config(arch).scaled_down()
    cache = get_model(cfg).init_cache(2, 24)
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    kinds = [leaf_kind(p) for p, _ in leaves]
    assert kinds.count("kv") == kv_leaves
    pages = ErdaKVPageStore(make_store("erda-cluster", n_shards=2, cfg=SMALL))
    for seq in (1, 2):
        pages.snapshot_cache(seq, cache)
    st = pages.stats
    assert st["snapshot_state_bytes"] + st["snapshot_kv_bytes"] == \
        st["snapshot_bytes"]
    kv = sum(np.asarray(a).nbytes for (p, a), k in zip(leaves, kinds)
             if k == "kv")
    state = sum(np.asarray(a).nbytes for (p, a), k in zip(leaves, kinds)
                if k == "state")
    # the encoded leaves carry a short header each
    assert 2 * kv <= st["snapshot_kv_bytes"] <= 2 * (kv + 200 * kv_leaves)
    assert 2 * state < st["snapshot_state_bytes"]
    if arch == "zamba2_7b":  # 12 layers' Mamba2 state, 2 calls' K/V
        state_leaves = {jax.tree_util.keystr(p)
                        for (p, _), k in zip(leaves, kinds) if k == "state"}
        assert state_leaves == {"['pos']"} | {
            f"['mamba'][{i}]['{n}']" for i in range(12) for n in ("conv", "h")}
