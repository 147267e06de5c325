"""Per-kernel allclose vs the pure-jnp oracles, with shape/dtype sweeps and
hypothesis property tests (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the dev dep
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mriq import mriq_pallas
from repro.kernels.rglru import rglru_pallas
from repro.kernels.ssd import ssd_pallas
from repro.kernels.swiglu import swiglu_pallas


def _keys(n, seed=0):
    return jax.random.split(jax.random.PRNGKey(seed), n)


# ---------------------------------------------------------------------------
# MRI-Q (the paper's application)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,bn,bm", [(64, 32, 16, 8), (128, 64, 64, 64),
                                       (256, 96, 32, 32)])
def test_mriq_blocks(n, m, bn, bm):
    k = _keys(7)
    kx, ky, kz = (jax.random.normal(k[i], (m,)) for i in range(3))
    phi = jax.random.uniform(k[3], (m,))
    x, y, z = (jax.random.normal(k[4 + i], (n,)) for i in range(3))
    qr, qi = mriq_pallas(kx, ky, kz, phi, x, y, z, block_n=bn, block_m=bm)
    qr0, qi0 = ref.mriq_ref(kx, ky, kz, phi, x, y, z)
    np.testing.assert_allclose(qr, qr0, atol=5e-4, rtol=1e-4)
    np.testing.assert_allclose(qi, qi0, atol=5e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32),
                                           (False, 0)])
def test_flash_attention_sweep(dtype, hq, hkv, causal, window):
    k = _keys(3)
    b, s, d = 2, 64, 16
    q = jax.random.normal(k[0], (b, s, hq, d), dtype)
    kk = jax.random.normal(k[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(k[2], (b, s, hkv, d), dtype)
    o = flash_attention(q, kk, v, causal=causal, window=window,
                        block_q=16, block_k=16)
    o0 = ref.flash_attention_ref(q, kk, v, causal, window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o0, np.float32), atol=tol,
                               rtol=tol)


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([32, 48, 64]),
       bq=st.sampled_from([8, 16, 32]),
       bk=st.sampled_from([8, 16]),
       seed=st.integers(0, 2**16))
def test_flash_attention_property(s, bq, bk, seed):
    """Block shape must never change the result (property)."""
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    b, hq, hkv, d = 1, 2, 1, 8
    q = jax.random.normal(k[0], (b, s, hq, d))
    kk = jax.random.normal(k[1], (b, s, hkv, d))
    v = jax.random.normal(k[2], (b, s, hkv, d))
    o = flash_attention(q, kk, v, block_q=min(bq, s), block_k=min(bk, s))
    o0 = ref.flash_attention_ref(q, kk, v)
    np.testing.assert_allclose(o, o0, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,w,bt,bw", [(32, 64, 8, 16), (64, 128, 16, 128),
                                       (128, 96, 32, 32)])
def test_rglru_blocks(s, w, bt, bw):
    k = _keys(2)
    b = 2
    log_a = -jnp.abs(jax.random.normal(k[0], (b, s, w))) * 0.2
    bb = jax.random.normal(k[1], (b, s, w)) * 0.5
    h = rglru_pallas(log_a, bb, block_w=bw, block_t=bt)
    h0 = ref.rglru_ref(log_a, bb)
    np.testing.assert_allclose(h, h0, atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), s=st.sampled_from([16, 32, 64]))
def test_rglru_property_decay_bound(seed, s):
    """|h| is bounded by sum of |b| (contraction property, a<1)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    b, w = 1, 16
    log_a = -jnp.abs(jax.random.normal(k[0], (b, s, w))) - 1e-3
    bb = jax.random.normal(k[1], (b, s, w))
    h = ops.rglru(log_a, bb)
    bound = jnp.cumsum(jnp.abs(bb), axis=1) + 1e-4
    assert bool(jnp.all(jnp.abs(h) <= bound))


# ---------------------------------------------------------------------------
# SSD (mamba2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (128, 64)])
def test_ssd_blocks(s, chunk):
    k = _keys(5)
    b, h, p, n = 2, 3, 8, 4
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(k[2], (h,)) * 0.2)
    Bm = jax.random.normal(k[3], (b, s, n))
    Cm = jax.random.normal(k[4], (b, s, n))
    y, hs = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk)
    y0, hs0 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y, y0, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hs, hs0, atol=1e-4, rtol=1e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16),
       chunk=st.sampled_from([4, 8, 16, 32]))
def test_ssd_property_chunk_invariance(seed, chunk):
    """Chunk size must not change the SSD result."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, s, h, p, n = 1, 32, 2, 4, 4
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(k[2], (h,)) * 0.1)
    Bm = jax.random.normal(k[3], (b, s, n))
    Cm = jax.random.normal(k[4], (b, s, n))
    y, hs = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk)
    y0, hs0 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=s)  # single chunk
    np.testing.assert_allclose(y, y0, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(hs, hs0, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# Fused SwiGLU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,d,f,bt,bf", [(32, 16, 32, 8, 8),
                                         (64, 32, 64, 32, 16),
                                         (128, 24, 48, 64, 48)])
def test_swiglu_blocks(t, d, f, bt, bf):
    k = _keys(4)
    x = jax.random.normal(k[0], (t, d))
    wi = jax.random.normal(k[1], (d, f)) * 0.2
    wg = jax.random.normal(k[2], (d, f)) * 0.2
    wo = jax.random.normal(k[3], (f, d)) * 0.2
    y = swiglu_pallas(x, wi, wg, wo, block_t=bt, block_f=bf)
    y0 = ref.swiglu_ref(x, wi, wg, wo)
    np.testing.assert_allclose(y, y0, atol=2e-5, rtol=2e-5)


def test_ssd_long_chunk_stays_finite():
    """At a published chunk length the segment sums above the diagonal
    overflow exp; the oracle and the kernel must mask them first."""
    k = _keys(5)
    b, s, h, p, n = 1, 256, 2, 8, 4
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)))   # sum >> 88
    A = -jnp.ones((h,))
    Bm = jax.random.normal(k[3], (b, s, n))
    Cm = jax.random.normal(k[4], (b, s, n))
    y0, hs0 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=s)
    assert bool(jnp.isfinite(y0).all() and jnp.isfinite(hs0).all())
    y, hs = ssd_pallas(x, dt, A, Bm, Cm, chunk=s)
    np.testing.assert_allclose(y, y0, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hs, hs0, atol=1e-4, rtol=1e-4)
