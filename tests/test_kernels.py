"""Per-kernel allclose tests vs the ref.py oracles, sweeping shapes and
dtypes (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,Sq,Sk,D", [
    (2, 4, 2, 128, 128, 64),
    (1, 4, 1, 256, 256, 32),       # MQA
    (2, 2, 2, 96, 96, 16),         # ragged block
    (1, 8, 2, 1, 512, 64),         # decode shape
    (1, 2, 2, 64, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(B, H, K, Sq, Sk, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, K, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, K, Sk, D), dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window", [16, 64, 128])
def test_flash_attention_sliding_window(window):
    B, H, S, D = 1, 2, 256, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    out = ops.flash_attention(q, k, v, window=window, block_q=32, block_k=32,
                              interpret=True)
    want = ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_softcap():
    B, H, S, D = 2, 2, 128, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D)) * 3
    k = jax.random.normal(ks[1], (B, H, S, D)) * 3
    v = jax.random.normal(ks[2], (B, H, S, D))
    out = ops.flash_attention(q, k, v, softcap=30.0, block_q=64, block_k=64,
                              interpret=True)
    want = ref.attention_ref(q, k, v, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,L,bt,bl", [
    (2, 64, 32, 16, 16),
    (1, 100, 48, 32, 32),          # ragged both dims
    (3, 128, 256, 128, 128),
    (1, 7, 8, 8, 8),               # shorter than one block
])
def test_rglru_scan(B, S, L, bt, bl):
    ks = jax.random.split(KEY, 3)
    log_a = -jnp.exp(jax.random.normal(ks[0], (B, S, L)) * 0.5 - 2)
    b = jax.random.normal(ks[1], (B, S, L))
    h0 = jax.random.normal(ks[2], (B, L))
    out = ops.rglru_scan(log_a, b, h0, block_t=bt, block_l=bl, interpret=True)
    want = ref.rglru_ref(log_a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


def test_rglru_scan_no_initial_state():
    B, S, L = 2, 32, 16
    ks = jax.random.split(KEY, 2)
    log_a = -jnp.exp(jax.random.normal(ks[0], (B, S, L)) * 0.3 - 2)
    b = jax.random.normal(ks[1], (B, S, L))
    out = ops.rglru_scan(log_a, b, None, block_t=8, block_l=8, interpret=True)
    want = ref.rglru_ref(log_a, b, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# RWKV-6 WKV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,N,C", [
    (2, 33, 2, 16, 8),             # ragged time
    (1, 64, 4, 64, 32),
    (2, 100, 3, 32, 32),
    (1, 16, 1, 8, 16),             # chunk > S
])
def test_wkv_chunked_kernel(B, S, H, N, C):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, S, H, N)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, N)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, N)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N)) * 0.5 - 1.5)
    u = jax.random.normal(ks[4], (H, N)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, N, N)) * 0.1
    y, st = ops.wkv(r, k, v, logw, u, s0, chunk=C, interpret=True)
    yw, stw = ref.wkv_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw), atol=5e-4,
                               rtol=5e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(stw), atol=5e-4,
                               rtol=5e-4)


def test_wkv_model_chunked_matches_sequential():
    """The model's pure-jnp chunked WKV equals the sequential oracle."""
    from repro.models.rwkv6 import wkv_chunked_ref
    B, S, H, N = 2, 48, 2, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, N)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, N)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, N)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N)) * 0.5 - 1.5)
    u = jax.random.normal(ks[4], (H, N)) * 0.5
    y, st = wkv_chunked_ref(r, k, v, logw, u, chunk=16)
    yw, stw = ref.wkv_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw), atol=5e-4,
                               rtol=5e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(stw), atol=5e-4,
                               rtol=5e-4)


# ---------------------------------------------------------------------------
# grouped GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,D,F", [
    (4, 32, 16, 24),
    (8, 128, 64, 128),
    (3, 100, 48, 60),              # ragged everything
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_group_gemm(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    n = jax.random.randint(ks[2], (E,), 0, C + 1)
    out = ops.group_gemm(x, w, n, interpret=True)
    want = ref.group_gemm_ref(x, w, n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype) * 10, rtol=_tol(dtype) * 10)


def test_group_gemm_zero_valid_rows():
    E, C, D, F = 3, 16, 8, 8
    x = jnp.ones((E, C, D))
    w = jnp.ones((E, D, F))
    n = jnp.array([0, 16, 5])
    out = np.asarray(ops.group_gemm(x, w, n, interpret=True))
    assert (out[0] == 0).all()
    assert (out[1] != 0).all()
    assert (out[2, 5:] == 0).all() and (out[2, :5] != 0).all()
