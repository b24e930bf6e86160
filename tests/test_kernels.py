"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes and
dtypes, plus property-style sweeps on the CG fusions.

The CG-fusion sweeps run over a fixed (n, coefficient, seed) grid covering
the edge shapes (n=1, block-1, block, block+1, multi-block) so the suite
collects and passes without ``hypothesis``; when hypothesis is installed the
same oracle checks additionally run fuzzed (see the *_fuzz tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False


def _qkv(key, B, S, H, KV, hd, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, S, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, S, KV, hd), jnp.float32).astype(dtype)
    return q, k, v


FA_CASES = [
    # (B, S, H, KV, hd, blk, causal, window, dtype)
    (1, 128, 1, 1, 64, 64, True, None, jnp.float32),
    (2, 256, 4, 2, 64, 128, True, None, jnp.float32),
    (1, 256, 4, 4, 32, 64, False, None, jnp.float32),
    (1, 256, 2, 1, 64, 64, True, 64, jnp.float32),     # sliding window
    (2, 128, 8, 2, 128, 64, True, None, jnp.bfloat16), # GQA bf16
    (1, 512, 2, 2, 64, 128, True, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,KV,hd,blk,causal,window,dtype", FA_CASES)
def test_flash_attention_matches_ref(B, S, H, KV, hd, blk, causal, window, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, KV, hd, dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              blk_q=blk, blk_k=blk, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_uneven_blocks():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 384, 2, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, blk_q=128, blk_k=128, interpret=True)
    expected = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5)


# ------------------------------------------- flash backward / JVP kernels --
FA_AD_CASES = [
    # (B, S, H, KV, hd, blk, causal, window, valid_len)
    (1, 128, 1, 1, 64, 64, True, None, None),
    (2, 128, 4, 2, 32, 64, True, None, None),      # GQA
    (1, 256, 4, 4, 32, 128, False, None, None),    # non-causal (encoder)
    (1, 256, 2, 1, 64, 64, True, 64, None),        # sliding window + GQA
    (1, 256, 2, 2, 32, 128, False, None, 130),     # padded tail, non-causal
    (1, 256, 2, 1, 32, 128, True, None, 130),      # padded tail, causal GQA
]


def _fa_ad_inputs(B, S, H, KV, hd):
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    q, k, v = _qkv(ks[0], B, S, H, KV, hd, jnp.float32)
    do = jax.random.normal(ks[3], (B, S, H, hd), jnp.float32)
    qt = jax.random.normal(ks[4], (B, S, H, hd), jnp.float32)
    kt = jax.random.normal(ks[5], (B, S, KV, hd), jnp.float32)
    vt = jax.random.normal(ks[6], (B, S, KV, hd), jnp.float32)
    return q, k, v, do, qt, kt, vt


@pytest.mark.parametrize("B,S,H,KV,hd,blk,causal,window,valid_len", FA_AD_CASES)
def test_flash_fwd_lse_matches_ref(B, S, H, KV, hd, blk, causal, window, valid_len):
    q, k, v, *_ = _fa_ad_inputs(B, S, H, KV, hd)
    kw = dict(causal=causal, window=window, valid_len=valid_len)
    o, lse = ops.flash_attention_fwd(q, k, v, blk_q=blk, blk_k=blk,
                                     interpret=True, **kw)
    o_r, lse_r = ref.flash_attention_fwd_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,H,KV,hd,blk,causal,window,valid_len", FA_AD_CASES)
def test_flash_bwd_matches_ref_and_ad(B, S, H, KV, hd, blk, causal, window, valid_len):
    """dQ / dK+dV Pallas passes vs the explicit-formula reference, and the
    reference vs jax AD of the dense forward (oracle of the oracle)."""
    q, k, v, do, *_ = _fa_ad_inputs(B, S, H, KV, hd)
    kw = dict(causal=causal, window=window, valid_len=valid_len)
    o, lse = ref.flash_attention_fwd_ref(q, k, v, **kw)
    dq, dk, dv = ops.flash_attention_bwd(q, k, v, o, lse, do, blk_q=blk,
                                         blk_k=blk, interpret=True, **kw)
    dq_r, dk_r, dv_r = ref.flash_attention_bwd_ref(q, k, v, o, lse, do, **kw)
    _, vjp = jax.vjp(lambda *a: ref.flash_attention_ref(*a, **kw), q, k, v)
    dq_a, dk_a, dv_a = vjp(do)
    for got, want, oracle in ((dq, dq_r, dq_a), (dk, dk_r, dk_a), (dv, dv_r, dv_a)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(want), np.asarray(oracle),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,H,KV,hd,blk,causal,window,valid_len", FA_AD_CASES)
def test_flash_jvp_matches_ref_and_ad(B, S, H, KV, hd, blk, causal, window, valid_len):
    q, k, v, _, qt, kt, vt = _fa_ad_inputs(B, S, H, KV, hd)
    kw = dict(causal=causal, window=window, valid_len=valid_len)
    o, lse = ref.flash_attention_fwd_ref(q, k, v, **kw)
    ot, lset = ops.flash_attention_jvp(q, k, v, o, lse, qt, kt, vt, blk_q=blk,
                                       blk_k=blk, interpret=True, **kw)
    ot_r, lset_r = ref.flash_attention_jvp_ref(q, k, v, o, lse, qt, kt, vt, **kw)
    _, ot_a = jax.jvp(lambda *a: ref.flash_attention_ref(*a, **kw),
                      (q, k, v), (qt, kt, vt))
    np.testing.assert_allclose(np.asarray(ot), np.asarray(ot_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lset), np.asarray(lset_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ot_r), np.asarray(ot_a), rtol=2e-4, atol=2e-4)


# Fixed property grid: edge shapes around the VMEM block boundary plus
# coefficient signs/magnitudes. Deterministic — no hypothesis required.
NS = [1, 127, 65_535, 65_536, 65_537, 200_000]
COEFFS = [(0.5, 0.25), (-2.7, 3.0), (0.0, -1.0)]


def _check_x_update(n, alpha, gamma, seed):
    key = jax.random.PRNGKey(seed)
    x, p, s = (jax.random.normal(k, (n,), jnp.float32)
               for k in jax.random.split(key, 3))
    out = ops.bicgstab_x_update(x, p, s, alpha, gamma, interpret=True)
    expected = ref.bicgstab_x_update_ref(x, p, s, alpha, gamma)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5)


def _check_residual_dots(n, gamma, seed):
    key = jax.random.PRNGKey(seed)
    s, As, r0s = (jax.random.normal(k, (n,), jnp.float32)
                  for k in jax.random.split(key, 3))
    r, d1, d2 = ops.bicgstab_residual_dots(s, As, r0s, gamma, interpret=True)
    er, _, _ = ref.bicgstab_residual_dots_ref(s, As, r0s, gamma)
    np.testing.assert_allclose(np.asarray(r), np.asarray(er), rtol=1e-5, atol=1e-5)
    # The dots against float64 sums of the same residual: an f32 reduction
    # of ~1e5 products (the jnp reference's own) can miss a cancelling sum
    # like r·r0s by more than atol.
    r64 = np.asarray(er, np.float64)
    np.testing.assert_allclose(float(d1), r64 @ np.asarray(r0s, np.float64),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(float(d2), r64 @ r64, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("alpha,gamma", COEFFS)
def test_x_update_property(n, alpha, gamma):
    _check_x_update(n, alpha, gamma, seed=n)


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("gamma", [0.3, -1.9])
def test_residual_dots_property(n, gamma):
    _check_residual_dots(n, gamma, seed=n + 1)


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=200_000),
        alpha=st.floats(min_value=-3, max_value=3, allow_nan=False),
        gamma=st.floats(min_value=-3, max_value=3, allow_nan=False),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_x_update_fuzz(n, alpha, gamma, seed):
        _check_x_update(n, alpha, gamma, seed)

    @settings(max_examples=20, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=200_000),
        gamma=st.floats(min_value=-3, max_value=3, allow_nan=False),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_residual_dots_fuzz(n, gamma, seed):
        _check_residual_dots(n, gamma, seed)


@pytest.mark.parametrize("n", [1, 127, 16384, 16385, 70_000])
@pytest.mark.parametrize("su,sv", [(1, 1), (3, 5), (8, 8), (9, 17)])
def test_gram_block_matches_matmul(n, su, sv):
    """The s-step Gram kernel: per-column-block partials of U @ Vᵀ across
    edge shapes (sub-block, block, block+1, multi-block columns; row counts
    off the sublane tile)."""
    key = jax.random.PRNGKey(n + su)
    U = jax.random.normal(key, (su, n), jnp.float32)
    V = jax.random.normal(jax.random.fold_in(key, 1), (sv, n), jnp.float32)
    G = ops.gram_block(U, V, interpret=True)
    assert G.shape == (su, sv)
    ref_G = np.asarray(U) @ np.asarray(V).T
    scale = max(float(np.abs(ref_G).max()), 1.0)
    np.testing.assert_allclose(np.asarray(G), ref_G, rtol=1e-4,
                               atol=1e-5 * scale * n ** 0.5)


@pytest.mark.parametrize("n", [1, 127, 4096, 65536, 65537, 300_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dot2_shapes_dtypes(n, dtype):
    key = jax.random.PRNGKey(n)
    u = jax.random.normal(key, (n,), jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32).astype(dtype)
    d1, d2 = ops.dot2(u, v, interpret=True)
    e1, e2 = ref.dot2_ref(u, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(float(d1), float(e1), rtol=tol, atol=tol * n ** 0.5)
    np.testing.assert_allclose(float(d2), float(e2), rtol=tol, atol=tol * n ** 0.5)
