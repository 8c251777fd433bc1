"""Layer-level oracles: every chunked/scanned implementation must match its
naive dense/sequential reference in fp32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import attention as A
from repro.models.layers import rwkv as R
from repro.models.layers import ssm as S

pytestmark = pytest.mark.slow  # JAX model/train lane; excluded from tier-1


def f32cfg(arch, **kw):
    cfg = get_config(arch).scaled_down()
    return dataclasses.replace(cfg, dtype="float32", **kw)


# ------------------------------------------------------------------- attention
@pytest.mark.parametrize("S_,H,KV,hd,chunk", [(64, 4, 2, 16, 16), (128, 4, 4, 8, 32),
                                              (96, 8, 2, 16, 32)])
def test_chunked_attention_matches_dense(S_, H, KV, hd, chunk):
    cfg = dataclasses.replace(f32cfg("olmo_1b"), attn_chunk=chunk)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, S_, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, S_, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, S_, KV, hd)), jnp.float32)
    got = A.chunked_attention(q, k, v, cfg)
    want = A.full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_decode_attention_matches_last_row_of_dense():
    rng = np.random.default_rng(2)
    B, S_, H, KV, hd = 2, 24, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S_, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S_, KV, hd)), jnp.float32)
    kv_pos = jnp.arange(S_)
    got = A.decode_attention(q, k, v, kv_pos, S_ - 1)
    qf = jnp.concatenate([jnp.zeros((B, S_ - 1, H, hd)), q], axis=1)
    want = A.full_attention(qf, k, v)[:, -1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------------- ssm
def ssm_sequential_ref(x, B_in, C_in, dt, A_, D):
    """Naive per-token recurrence."""
    Bsz, S_, nh, hp = x.shape
    ds = B_in.shape[-1]
    h = np.zeros((Bsz, nh, hp, ds))
    ys = np.zeros_like(np.asarray(x))
    for t in range(S_):
        decay = np.exp(np.asarray(dt[:, t]) * np.asarray(A_))       # (B,nh)
        h = h * decay[..., None, None] + np.einsum(
            "bs,bhp,bh->bhps", np.asarray(B_in[:, t]), np.asarray(x[:, t]),
            np.asarray(dt[:, t]))
        ys[:, t] = np.einsum("bs,bhps->bhp", np.asarray(C_in[:, t]), h)
    return ys + np.asarray(x) * np.asarray(D)[None, None, :, None]


@pytest.mark.parametrize("S_,chunk", [(32, 8), (64, 16), (48, 16)])
def test_ssm_chunked_matches_sequential(S_, chunk):
    cfg = dataclasses.replace(f32cfg("zamba2_7b"), ssm_chunk=chunk)
    rng = np.random.default_rng(3)
    Bsz, nh, hp, ds = 2, 4, 8, 16
    x = jnp.asarray(rng.standard_normal((Bsz, S_, nh, hp)), jnp.float32)
    B_in = jnp.asarray(rng.standard_normal((Bsz, S_, ds)), jnp.float32)
    C_in = jnp.asarray(rng.standard_normal((Bsz, S_, ds)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (Bsz, S_, nh)), jnp.float32)
    A_ = -jnp.asarray(rng.uniform(0.5, 2.0, (nh,)), jnp.float32)
    D = jnp.asarray(rng.standard_normal((nh,)), jnp.float32)
    y, h = S.ssm_chunked(cfg, x, B_in[:, :, None], C_in[:, :, None], dt, A_)  # one group
    y = y + x * D[None, None, :, None]
    want = ssm_sequential_ref(x, B_in, C_in, dt, A_, D)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)


def test_ssm_decode_continues_chunked():
    """State handoff: chunked(S) then decode(1) ≡ chunked(S+1)."""
    cfg = f32cfg("zamba2_7b")
    model_cfg = dataclasses.replace(cfg, ssm_chunk=8)
    rng = np.random.default_rng(4)
    key = jax.random.PRNGKey(0)
    p = S.init_ssm(model_cfg, key)
    x = jnp.asarray(rng.standard_normal((2, 17, model_cfg.d_model)), jnp.float32) * 0.1
    y_full, _ = S.apply_ssm(p, x, model_cfg, None)
    y_pre, st = S.apply_ssm(p, x[:, :16], model_cfg, None)
    y_step, _ = S.decode_ssm(p, x[:, 16:17], model_cfg, st)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full[:, 16:17]),
                               rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------------ rwkv
def wkv_sequential_ref(r, k, v, w, u):
    B, S_, H, hd = np.asarray(r).shape
    h = np.zeros((B, H, hd, hd))
    ys = np.zeros_like(np.asarray(v))
    r, k, v, w = (np.asarray(a, np.float64) for a in (r, k, v, w))
    u = np.asarray(u, np.float64)
    for t in range(S_):
        kv = np.einsum("bhd,bhe->bhde", k[:, t], v[:, t])
        ys[:, t] = np.einsum("bhd,bhde->bhe", r[:, t], h + u[None, :, :, None] * kv)
        h = w[:, t][..., None] * h + kv
    return ys


@pytest.mark.parametrize("S_,chunk", [(32, 8), (64, 16)])
def test_wkv_chunked_matches_sequential(S_, chunk):
    rng = np.random.default_rng(5)
    B, H, hd = 2, 2, 8
    r = jnp.asarray(rng.standard_normal((B, S_, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S_, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S_, H, hd)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.85, 0.999, (B, S_, H, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    h0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y, _ = R.wkv_chunked(r, k, v, w, u, h0, chunk)
    want = wkv_sequential_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), want, rtol=5e-4, atol=5e-4)


def test_wkv_state_handoff():
    rng = np.random.default_rng(6)
    B, S_, H, hd = 1, 24, 2, 8
    mk = lambda: jnp.asarray(rng.standard_normal((B, S_, H, hd)), jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.9, 0.999, (B, S_, H, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    h0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y_full, h_full = R.wkv_chunked(r, k, v, w, u, h0, 8)
    y1, h1 = R.wkv_chunked(r[:, :16], k[:, :16], v[:, :16], w[:, :16], u, h0, 8)
    y2, h2 = R.wkv_chunked(r[:, 16:], k[:, 16:], v[:, 16:], w[:, 16:], u, h1, 8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=1e-4, atol=1e-4)
