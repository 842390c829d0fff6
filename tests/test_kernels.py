"""Pallas kernel validation: shape/dtype sweeps + hypothesis property tests
against the pure-jnp oracles (interpret mode on CPU)."""
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.kernels import ops, ref

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, S, H, K, D, window, dtype)
    (2, 256, 4, 2, 64, None, jnp.float32),
    (1, 256, 8, 8, 128, None, jnp.float32),
    (2, 256, 4, 1, 64, 128, jnp.float32),
    (1, 512, 4, 2, 128, None, jnp.float32),
    (1, 256, 4, 2, 256, None, jnp.float32),      # gemma-style head_dim 256
    (2, 256, 4, 2, 64, None, jnp.bfloat16),
    (1, 384, 6, 2, 64, 256, jnp.float32),        # non-pow2 seq, SWA
]


@pytest.mark.parametrize("case", FLASH_CASES,
                         ids=[f"B{c[0]}S{c[1]}H{c[2]}K{c[3]}D{c[4]}w{c[5]}-{c[6].__name__}"
                              for c in FLASH_CASES])
def test_flash_attention_matches_oracle(case):
    b, s, h, kh, d, win, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(s + h), 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d)).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, sliding_window=win)
    exp = ref.flash_attention_ref(q, k, v, causal=True, sliding_window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


# the hypothesis property sweeps compile a fresh Pallas kernel per drawn
# shape (~1.5 s each on CPU): slow tier.  The fixed oracle grids above keep
# per-kernel coverage in the default tier.
@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.tuples(
    st.sampled_from([1, 2]),
    st.sampled_from([128, 256]),
    st.sampled_from([(4, 2), (4, 4), (8, 1)]),
    st.sampled_from([64, 128]),
    st.sampled_from([None, 64]),
))
def test_flash_attention_property(tup):
    b, s, (h, kh), d, win = tup
    ks = jax.random.split(jax.random.PRNGKey(b * s + h + d), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, sliding_window=win)
    exp = ref.flash_attention_ref(q, k, v, causal=True, sliding_window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5, rtol=1e-4)


def test_flash_attention_is_causal():
    """Future tokens must not influence earlier outputs."""
    b, s, h, kh, d = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    out1 = ops.flash_attention(q, k, v, causal=True)
    k2 = k.at[:, -1].add(100.0)
    v2 = v.at[:, -1].add(100.0)
    out2 = ops.flash_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (B, S, H, G, P, N, chunk)
    (2, 512, 4, 1, 64, 128, 256),
    (1, 256, 8, 2, 32, 64, 128),
    (1, 512, 4, 4, 64, 64, 128),
    (2, 256, 2, 1, 128, 128, 256),
]


def _ssd_inputs(b, s, h, g, p, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + s + n), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
    bm = jax.random.normal(ks[3], (b, s, g, n), jnp.float32) * 0.3
    cm = jax.random.normal(ks[4], (b, s, g, n), jnp.float32) * 0.3
    return x, dt, a, bm, cm


@pytest.mark.parametrize("case", SSD_CASES,
                         ids=[f"B{c[0]}S{c[1]}H{c[2]}G{c[3]}P{c[4]}N{c[5]}Q{c[6]}"
                              for c in SSD_CASES])
def test_ssd_scan_matches_oracle(case):
    b, s, h, g, p, n, chunk = case
    x, dt, a, bm, cm = _ssd_inputs(b, s, h, g, p, n)
    y, st_ = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk)
    ye, ste = ref.ssd_scan_ref(x, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(ste), atol=2e-3, rtol=1e-3)


def test_ssd_chunk_invariance():
    """The oracle must give identical results for any chunking."""
    x, dt, a, bm, cm = _ssd_inputs(1, 512, 4, 1, 32, 64)
    y1, s1 = ref.ssd_scan_ref(x, dt, a, bm, cm, chunk=64)
    y2, s2 = ref.ssd_scan_ref(x, dt, a, bm, cm, chunk=512)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-3, rtol=1e-3)


def test_ssd_grad_finite_at_long_chunks():
    """A 256-step chunk with strong decay (the mamba2-370m chunk size)
    drives the masked upper triangle of the decay matrix past exp's float32
    range; the masked entries must not poison the gradient."""
    x, dt, _, bm, cm = _ssd_inputs(1, 256, 2, 1, 8, 16, seed=3)
    a = jnp.full((2,), -16.0)
    loss = lambda x, dt, a: jnp.sum(ref.ssd_scan_ref(x, dt, a, bm, cm,
                                                     chunk=256)[0] ** 2)
    for g in jax.grad(loss, argnums=(0, 1, 2))(x, dt, a):
        assert np.all(np.isfinite(np.asarray(g)))


def test_ssd_matches_naive_recurrence():
    """Oracle == literal per-step recurrence h_t = a_t h_{t-1} + dt B x."""
    b, s, h, g, p, n = 1, 64, 2, 1, 8, 16
    x, dt, a, bm, cm = _ssd_inputs(b, s, h, g, p, n, seed=9)
    y, _ = ref.ssd_scan_ref(x, dt, a, bm, cm, chunk=32)
    rep = h // g
    bmh = jnp.repeat(bm, rep, axis=2)
    cmh = jnp.repeat(cm, rep, axis=2)
    state = np.zeros((b, h, p, n), np.float32)
    outs = np.zeros((b, s, h, p), np.float32)
    for t in range(s):
        at = np.exp(np.asarray(dt[:, t] * a))                 # (b,h)
        dax = np.asarray(dt[:, t, :, None] * x[:, t])         # (b,h,p)
        state = state * at[..., None, None] + dax[..., None] * np.asarray(bmh[:, t])[:, :, None, :]
        outs[:, t] = np.einsum("bhpn,bhn->bhp", state, np.asarray(cmh[:, t]))
    np.testing.assert_allclose(np.asarray(y), outs, atol=2e-3, rtol=1e-3)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(st.tuples(
    st.sampled_from([1, 2]),
    st.sampled_from([128, 256]),
    st.sampled_from([(2, 1), (4, 2)]),
    st.sampled_from([(32, 64), (64, 128)]),
))
def test_ssd_property(tup):
    b, s, (h, g), (p, n) = tup
    x, dt, a, bm, cm = _ssd_inputs(b, s, h, g, p, n, seed=b + s)
    y, st_ = ops.ssd_scan(x, dt, a, bm, cm, chunk=128)
    ye, ste = ref.ssd_scan_ref(x, dt, a, bm, cm, chunk=128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-3, rtol=1e-3)
