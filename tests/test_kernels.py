"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref


# ------------------------------------------------------------- attention

@pytest.mark.parametrize("bh,s,d,causal,window,dtype", [
    (2, 256, 64, True, None, jnp.float32),
    (1, 200, 64, True, None, jnp.float32),     # non-multiple of block
    (2, 384, 64, True, 128, jnp.float32),      # sliding window
    (3, 64, 128, False, None, jnp.float32),    # bidirectional
    (2, 256, 64, True, None, jnp.bfloat16),    # low precision
    (1, 128, 32, True, 32, jnp.float32),       # window < block
])
def test_flash_attention_matches_ref(bh, s, d, causal, window, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (bh, s, d), dtype)
    k = jax.random.normal(ks[1], (bh, s, d), dtype)
    v = jax.random.normal(ks[2], (bh, s, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@settings(deadline=None, max_examples=12)
@given(st.integers(1, 3), st.integers(16, 300), st.integers(1, 2))
def test_flash_attention_property(bh, s, dpow):
    d = 32 * dpow
    key = jax.random.PRNGKey(s)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (bh, s, d))
    k = jax.random.normal(ks[1], (bh, s, d))
    v = jax.random.normal(ks[2], (bh, s, d))
    out = ops.flash_attention(q, k, v, causal=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5,
                               rtol=3e-5)


# ------------------------------------------------------------------ SSD

@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 256, 3, 32, 16, 64),
    (1, 128, 1, 64, 32, 128),
    (2, 192, 2, 32, 16, 64),     # 3 chunks
    (1, 100, 2, 32, 16, 32),     # padding path via ops.ssd
])
def test_ssd_kernel_matches_sequential(b, s, h, p, n, chunk):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.2
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    y_k = ops.ssd(x, dt, a, bm, cm, chunk=chunk)
    y_r, _ = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=2e-4,
                               rtol=2e-3)


def test_ssd_chunked_jnp_matches_sequential_with_state():
    b, s, h, p, n, chunk = 2, 256, 3, 16, 8, 64
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.2
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    y_c, h_c = ref.ssd_chunked_ref(x, dt, a, bm, cm, chunk=chunk)
    y_r, h_r = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r), atol=2e-4,
                               rtol=2e-3)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_r), atol=2e-4,
                               rtol=2e-3)


def test_ssd_decode_step_matches_scan():
    """Running decode steps one-by-one equals the full sequential scan."""
    b, s, h, p, n = 1, 16, 2, 8, 4
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.2
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    y_r, _ = ref.ssd_ref(x, dt, a, bm, cm)
    hstate = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(s):
        yt, hstate = ops.ssd_decode_step(hstate, x[:, t], dt[:, t], a,
                                         bm[:, t], cm[:, t])
        ys.append(yt)
    y_d = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_r), atol=1e-5,
                               rtol=1e-4)

