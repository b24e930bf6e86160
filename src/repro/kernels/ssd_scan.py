"""SSD (Mamba2) intra-chunk kernel: the quadratic hot-spot of the chunked
state-space scan, as a Pallas TPU kernel.

Per (batch, chunk, head) grid step, entirely in VMEM:
    l        = cumsum(log_a)                       (Q,)
    scores   = C Bᵀ                                (Q,Q)   [MXU]
    decay    = exp(l_i − l_j) · causal_mask        (Q,Q)
    y_intra  = (scores ⊙ decay) u                  (Q,P)   [MXU]
    S_chunk  = Bᵀ (u ⊙ exp(l_Q − l))               (N,P)   [MXU]
    g        = exp(l_Q)                            scalar

The O(L/Q) inter-chunk combination (associative scan over (g, S) + the
rank-1 correction C·h_prev·exp(l)) stays in jnp — it is tiny and latency
bound, not compute bound. Forward-only (deployment path), validated against
the pure-jnp ``ssm.ssd_chunked`` oracle in interpret mode.

Block shapes: Q (chunk) and P (head_dim) are the MXU dims — keep them at
128/64; N (state) ≤ 256 rides along in VMEM. VMEM footprint per step ≈
Q·(2N + 2P + Q) · 4B ≈ 0.3 MB at Q=128, N=P=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_intra_kernel(u_ref, la_ref, b_ref, c_ref, y_ref, s_ref, g_ref, l_ref, *, Q):
    u = u_ref[0, 0, 0].astype(jnp.float32)            # (Q, P)
    la = la_ref[0, 0, 0].astype(jnp.float32)          # (Q,)
    B = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)               # (Q, N)

    l = jnp.cumsum(la)                                 # (Q,)
    rel = l[:, None] - l[None, :]                      # l_i - l_j
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )
    decay = jnp.where(causal, jnp.exp(rel), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)   # (Q,Q)
    y = jax.lax.dot_general(scores * decay, u, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)        # (Q,P)
    s_dec = jnp.exp(l[-1] - l)                         # (Q,)
    S = jax.lax.dot_general(B, u * s_dec[:, None], (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)        # (N,P)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    s_ref[0, 0, 0] = S.astype(s_ref.dtype)
    g_ref[0, 0, 0] = jnp.exp(l[-1])
    l_ref[0, 0, 0] = l.astype(l_ref.dtype)


def ssd_intra(u, log_a, Bv, Cv, *, interpret=False):
    """u: (B,nc,H,Q,P); log_a: (B,nc,H,Q); Bv/Cv: (B,nc,Q,N) (shared heads).

    Returns (y_intra: (B,nc,H,Q,P), S: (B,nc,H,N,P), g: (B,nc,H),
             l: (B,nc,H,Q))."""
    Bb, nc, H, Q, P = u.shape
    N = Bv.shape[-1]
    kernel = functools.partial(_ssd_intra_kernel, Q=Q)
    y, S, g, l = pl.pallas_call(
        kernel,
        name="ssd_intra",
        grid=(Bb, nc, H),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, c, h: (b, c, h, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, c, h: (b, c, h)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, c, h: (b, c, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, nc, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, nc, H, N, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, nc, H), jnp.float32),
            jax.ShapeDtypeStruct((Bb, nc, H, Q), jnp.float32),
        ],
        interpret=interpret,
    )(u, log_a, Bv, Cv)
    return y, S, g, l


def ssd_chunked_pallas(u, log_a, Bv, Cv, chunk: int, h0=None, *, interpret=False):
    """Drop-in for ``ssm.ssd_chunked`` (shared-heads B/C) with the intra-chunk
    work in the Pallas kernel and the inter-chunk scan in jnp."""
    Bb, L, H, P = u.shape
    assert L % chunk == 0
    nc, Q = L // chunk, chunk
    N = Bv.shape[-1]
    u_r = u.reshape(Bb, nc, Q, H, P).transpose(0, 1, 3, 2, 4)
    la_r = log_a.reshape(Bb, nc, Q, H).transpose(0, 1, 3, 2)
    Bv_r = Bv.reshape(Bb, nc, Q, N)
    Cv_r = Cv.reshape(Bb, nc, Q, N)
    y_intra, S, g, l = ssd_intra(u_r, la_r, Bv_r, Cv_r, interpret=interpret)

    def combine(left, right):
        g_l, s_l = left
        g_r, s_r = right
        return g_l * g_r, g_r[..., None, None] * s_l + s_r

    g_scan, S_scan = jax.lax.associative_scan(combine, (g, S), axis=1)
    if h0 is not None:
        h0 = h0.astype(jnp.float32)
        cumg = jnp.exp(jnp.cumsum(jnp.log(jnp.maximum(g, 1e-38)), axis=1))
        S_scan = S_scan + cumg[..., None, None] * h0[:, None]
    h_final = S_scan[:, -1]
    h_prev = jnp.concatenate(
        [h0[:, None] if h0 is not None else jnp.zeros_like(S_scan[:, :1]), S_scan[:, :-1]],
        axis=1,
    )
    y_inter = jnp.einsum("bcin,bchnp->bchip", Cv_r, h_prev) * jnp.exp(l)[..., None]
    y = (y_intra + y_inter).transpose(0, 1, 3, 2, 4).reshape(Bb, L, H, P)
    return y, h_final
