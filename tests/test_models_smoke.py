"""Per-architecture smoke tests: REDUCED same-family configs, one forward /
train-grad / prefill+decode step on CPU, asserting shapes + finiteness.
(The FULL configs run in the on-chip benchmark.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import make_batch
from repro.models import get_model
from repro.configs.base import ShapeConfig

pytestmark = pytest.mark.slow  # JAX model/train lane; excluded from tier-1

SMOKE_SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")
#: the architectures the on-chip benchmark serves
ARCHS = ["olmo_1b", "rwkv6_1p6b", "zamba2_7b"]


def setup_model(arch):
    cfg = get_config(arch).scaled_down()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), max_seq=SMOKE_SHAPE.seq_len + 8)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, SMOKE_SHAPE).items()}
    return cfg, model, params, batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_loss_finite(arch):
    cfg, model, params, batch = setup_model(arch)
    loss = jax.jit(model.train_loss)(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{arch}: loss={loss}"
    assert float(loss) > 0.1  # CE of an untrained model can't be ~0


@pytest.mark.parametrize("arch", ARCHS)
def test_train_grads_finite(arch):
    cfg, model, params, batch = setup_model(arch)
    loss, grads = jax.jit(jax.value_and_grad(model.train_loss))(params, batch)
    assert jnp.isfinite(loss)
    flat = jax.tree.leaves(grads)
    assert all(jnp.all(jnp.isfinite(g)) for g in flat), f"{arch}: non-finite grads"
    assert any(jnp.any(g != 0) for g in flat), f"{arch}: all-zero grads"


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode(arch):
    cfg, model, params, batch = setup_model(arch)
    logits, cache = jax.jit(model.prefill)(params, batch)
    B = SMOKE_SHAPE.global_batch
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    step = jax.jit(model.decode_step)
    for _ in range(3):
        logits, cache = step(params, cache, token)
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    """Teacher-forcing consistency: decoding token t with the prefill(0..t-1)
    cache must equal prefilling 0..t — same logits.  fp32 so that genuine
    protocol bugs aren't masked by (or blamed on) bf16 accumulation noise."""
    import dataclasses
    cfg = dataclasses.replace(get_config(arch).scaled_down(), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), max_seq=40)
    S = 16
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(2, S + 1)).astype(np.int32)
    batch_a = {"tokens": jnp.asarray(toks[:, :S])}
    batch_b = {"tokens": jnp.asarray(toks[:, : S + 1])}
    logits_a, cache = jax.jit(model.prefill)(params, batch_a)
    logits_step, _ = jax.jit(model.decode_step)(params, cache, jnp.asarray(toks[:, S : S + 1]))
    logits_b, _ = jax.jit(model.prefill)(params, batch_b)
    np.testing.assert_allclose(np.asarray(logits_step, np.float32),
                               np.asarray(logits_b, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_int8_kv_cache_decode_close_to_bf16():
    """cache_quant=True must keep decode logits close to the unquantized
    path."""
    import dataclasses
    cfg = dataclasses.replace(get_config("olmo_1b").scaled_down(),
                              dtype="float32")
    cfg_q = dataclasses.replace(cfg, cache_quant=True)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    nxt = rng.integers(0, cfg.vocab_size, size=(2, 1)).astype(np.int32)

    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    _, cache = jax.jit(m.prefill)(params, {"tokens": jnp.asarray(toks)})
    ref_logits, _ = jax.jit(m.decode_step)(params, cache, jnp.asarray(nxt))

    mq = get_model(cfg_q)
    cache_q = mq.init_cache(2, 0)  # empty cache (capacity CACHE_PAD ≥ 17)
    # replay the prefix through the quantized decode path
    logits_q = None
    for t in range(16):
        logits_q, cache_q = jax.jit(mq.decode_step)(
            params, cache_q, jnp.asarray(toks[:, t : t + 1]))
    logits_q, _ = jax.jit(mq.decode_step)(params, cache_q, jnp.asarray(nxt))
    # int8 quantization noise is bounded; rankings should agree closely
    a = np.asarray(ref_logits, np.float32).ravel()
    b = np.asarray(logits_q, np.float32).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.999, corr


def test_full_configs_match_assignment():
    """Pin the exact assigned hyperparameters."""
    c = get_config("rwkv6_1p6b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab_size) == (24, 2048, 7168, 65_536)
    c = get_config("zamba2_7b")
    assert (c.n_layers, c.d_model, c.n_heads, c.head_dim, c.d_ff, c.vocab_size,
            c.ssm_state, c.ssm_heads, c.ssm_groups, c.n_mem_blocks,
            c.adapter_rank, len(c.hybrid_layer_ids)) == \
        (81, 3584, 32, 224, 14_336, 32_000, 64, 112, 2, 2, 128, 13)
    c = get_config("olmo_1b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab_size) == (16, 2048, 8192, 50_304)
