"""The plain float32 references agree with the program's prefill and its
decode through the cache, at the program's smoke widths on the CPU, with the
model in float32 so the only differences are summation order (and the
program's chunked WKV against the reference's token-by-token recurrence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import spec
from tiny import tiny_config
from weights import make_init, seed_key


@pytest.mark.parametrize("name", ["olmo_1b", "rwkv6_1p6b"])
def test_reference_matches_program_prefill_and_decode(name):
    config = tiny_config(name)
    config["model"]["dtype"] = "float32"
    model = run.program_model(config)
    params = make_init(model.init_abstract(64), config["init"])(seed_key(7))
    rng = np.random.default_rng(0)
    V = config["model"]["vocab_size"]
    prompt = jnp.asarray(rng.integers(0, V, (2, 16)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache = jax.jit(model.prefill)(params, {"tokens": prompt})
        seq = [logits[:, 0]]
        tokens = prompt
        for _ in range(5):
            tok = jnp.argmax(seq[-1], -1).astype(jnp.int32)[:, None]
            tokens = jnp.concatenate([tokens, tok], axis=1)
            logits, cache = jax.jit(model.decode_step)(params, cache, tok)
            seq.append(logits[:, 0])
        ref = spec.family(config).logits(params, tokens, config["model"])
    got = np.stack([np.asarray(s) for s in seq], axis=1)
    want = np.asarray(ref[:, 15:])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)

