"""Pure-jnp oracles for every Pallas kernel (correctness ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _ref_mask(S, T=None, *, causal, window, valid_len):
    T = S if T is None else T
    qi = jnp.arange(S)[:, None]
    kj = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask = kj <= qi
    if window is not None:
        mask = jnp.logical_and(mask, kj > qi - window)
    if valid_len is not None:
        mask = jnp.logical_and(mask, kj < valid_len)
    return mask


def _ref_logits(q, k, scale, *, causal, window, valid_len, bias=None):
    """Masked (B,KV,G,Sq,Sk) logits + mask from grouped heads. ``bias``:
    optional (B|1, Sq, Sk) additive logit bias (explicit masks)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias[:, None, None]
    mask = _ref_mask(S, T, causal=causal, window=window, valid_len=valid_len)
    return jnp.where(mask[None, None, None], logits, NEG_INF), mask


def flash_attention_fwd_ref(q, k, v, *, causal=True, window=None,
                            valid_len=None, scale=None, bias=None):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd) -> (o: (B,Sq,H,hd), lse: (B,H,Sq)).
    GQA via head grouping; lse is the per-row logsumexp residual (0 for
    fully-masked rows, matching the kernel's guard)."""
    B, S, H, hd = q.shape
    scale = scale if scale is not None else 1.0 / jnp.sqrt(hd)
    logits, _ = _ref_logits(q, k, scale, causal=causal, window=window,
                            valid_len=valid_len, bias=bias)
    m = jnp.max(logits, axis=-1)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    l = jnp.sum(jnp.exp(logits - m_safe[..., None]), axis=-1)
    lse = m_safe + jnp.log(jnp.where(l <= 0.0, 1.0, l))
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (
        out.reshape(B, S, H, hd).astype(q.dtype),
        lse.reshape(B, H, S),
    )


def flash_attention_ref(q, k, v, *, causal=True, window=None, valid_len=None,
                        scale=None, bias=None):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd) -> (B,Sq,H,hd). GQA via grouping."""
    return flash_attention_fwd_ref(q, k, v, causal=causal, window=window,
                                   valid_len=valid_len, scale=scale,
                                   bias=bias)[0]


def _ref_p(q, k, lse, scale, *, causal, window, valid_len, bias=None):
    """(B,KV,G,Sq,Sk) attention weights recomputed from the stored lse."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    logits, mask = _ref_logits(q, k, scale, causal=causal, window=window,
                               valid_len=valid_len, bias=bias)
    lseg = lse.reshape(B, KV, H // KV, S)
    return jnp.where(mask[None, None, None],
                     jnp.exp(logits - lseg[..., None]), 0.0)


def flash_attention_bwd_ref(q, k, v, o, lse, do, *, causal=True, window=None,
                            valid_len=None, scale=None, bias=None):
    """Dense-jnp backward from the stored lse: returns (dq, dk, dv).

    dP = dO Vᵀ, Δ = rowsum(dO ∘ O), dS = P ∘ (dP − Δ);
    dQ = scale·dS K, dK = scale·dSᵀ Q, dV = Pᵀ dO (GQA group-summed).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / jnp.sqrt(hd)
    p = _ref_p(q, k, lse, scale, causal=causal, window=window,
               valid_len=valid_len, bias=bias)
    qg = q.reshape(B, S, KV, G, hd)
    dog = do.reshape(B, S, KV, G, hd).astype(jnp.float32)
    delta = jnp.einsum("bshd,bshd->bsh", o.astype(jnp.float32),
                       do.astype(jnp.float32)).reshape(B, S, KV, G)
    dp = jnp.einsum("bskgh,btkh->bkgst", dog, v,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta.transpose(0, 2, 3, 1)[..., None])
    dq = scale * jnp.einsum("bkgst,btkh->bskgh", ds, k,
                            preferred_element_type=jnp.float32)
    dk = scale * jnp.einsum("bkgst,bskgh->btkh", ds, qg,
                            preferred_element_type=jnp.float32)
    dv = jnp.einsum("bkgst,bskgh->btkh", p, dog,
                    preferred_element_type=jnp.float32)
    return (dq.reshape(B, S, H, hd).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


def flash_attention_jvp_ref(q, k, v, o, lse, qt, kt, vt, *, causal=True,
                            window=None, valid_len=None, scale=None,
                            bias=None):
    """Dense-jnp tangent from the stored lse: returns (ȯ, l̇se).

    Ṡ = scale·(Q̇Kᵀ + QK̇ᵀ), t = rowsum(P ∘ Ṡ);
    ȯ = Σ_j P_ij (Ṡ_ij v_j + v̇_j) − t ∘ o, l̇se = t.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / jnp.sqrt(hd)
    p = _ref_p(q, k, lse, scale, causal=causal, window=window,
               valid_len=valid_len, bias=bias)
    qg = q.reshape(B, S, KV, G, hd)
    qtg = qt.reshape(B, S, KV, G, hd)
    st = scale * (
        jnp.einsum("bskgh,btkh->bkgst", qtg, k,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bskgh,btkh->bkgst", qg, kt,
                     preferred_element_type=jnp.float32)
    )
    r = p * st
    g = (jnp.einsum("bkgst,btkh->bskgh", r, v,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bkgst,btkh->bskgh", p, vt,
                      preferred_element_type=jnp.float32))
    t = jnp.sum(r, axis=-1)                                   # (B,KV,G,S)
    t_bsh = t.transpose(0, 3, 1, 2).reshape(B, S, H)
    ot = g.reshape(B, S, H, hd) - t_bsh[..., None] * o.astype(jnp.float32)
    return ot.astype(o.dtype), t.reshape(B, H, S)


def flash_decode_ref(q, k, v, bias, *, scale=None):
    """Dense decode oracle. q: (B,H,hd), k/v: (B,W,KV,hd), bias: (B|1,W)
    additive mask row (0 attendable / NEG_INF masked) -> (B,H,hd).
    Independent dense softmax — ground truth for the split-K kernel."""
    B, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / jnp.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = logits + bias[:, None, None, :]
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd).astype(q.dtype)


def flash_decode_paged_ref(q, k_pool, v_pool, page_table, bias, *, scale=None):
    """Paged decode oracle: gather the logical KV in jnp (dense copy — the
    thing the kernel avoids) then run the dense oracle."""
    B = q.shape[0]
    ps = k_pool.shape[1]
    pages = jnp.maximum(page_table, 0)                       # (B, maxp)
    k = k_pool[pages].reshape(B, -1, *k_pool.shape[2:])      # (B, maxp*ps, KV, hd)
    v = v_pool[pages].reshape(B, -1, *v_pool.shape[2:])
    return flash_decode_ref(q, k, v, bias, scale=scale)


def bicgstab_x_update_ref(x, p, s, alpha, gamma):
    """x + alpha*p + gamma*s in f32."""
    return (x.astype(jnp.float32) + alpha * p.astype(jnp.float32)
            + gamma * s.astype(jnp.float32))


def bicgstab_residual_dots_ref(s, As, r0s, gamma):
    """r = s - gamma*As; returns (r, <r,r0s>, <r,r>)."""
    r = s.astype(jnp.float32) - gamma * As.astype(jnp.float32)
    return r, jnp.vdot(r, r0s.astype(jnp.float32)), jnp.vdot(r, r)


def dot2_ref(u, v):
    """(<u,v>, <v,v>) in f32."""
    uf, vf = u.astype(jnp.float32), v.astype(jnp.float32)
    return jnp.vdot(uf, vf), jnp.vdot(vf, vf)


def mlp_hvp_ref(acts, errs, p, y, weights, dweights, dbiases, *,
                mxu_dtype=jnp.bfloat16):
    """The tanh MLP's exact Hessian-vector product (kernels/mlp_hvp.py's
    R-operator) over all rows at once, each matmul's operands rounded to
    ``mxu_dtype`` and accumulated in f32, as the kernel rounds them; at
    ``mxu_dtype=float32`` (and ``highest`` precision) it is
    ``jax.jvp(jax.grad(loss))``. Returns (d∇W list, d∇b list)."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST

    def mm(u, w, dims):
        return jax.lax.dot_general(u.astype(mxu_dtype), w.astype(mxu_dtype),
                                   (dims, ((), ())), precision=hi,
                                   preferred_element_type=f32)

    L, n = len(weights), p.shape[0]
    da, dz = [None], mm(acts[0], dweights[0], ((1,), (0,))) + dbiases[0]
    for l in range(1, L):
        da.append((1.0 - acts[l] * acts[l]) * dz)
        dz = (mm(da[l], weights[l], ((1,), (0,)))
              + mm(acts[l], dweights[l], ((1,), (0,))) + dbiases[l])
    inv_b = 1.0 / n
    delta = (p - jax.nn.one_hot(y, p.shape[1], dtype=f32)) * inv_b
    ddelta = p * (dz - jnp.sum(p * dz, axis=1, keepdims=True)) * inv_b
    gw, gb = [None] * L, [None] * L
    for l in reversed(range(L)):
        gw[l] = mm(acts[l], ddelta, ((0,), (0,)))
        if l:
            gw[l] = gw[l] + mm(da[l], delta, ((0,), (0,)))
        gb[l] = jnp.sum(ddelta, axis=0)
        if l:
            de = (mm(ddelta, weights[l], ((1,), (1,)))
                  + mm(delta, dweights[l], ((1,), (1,))))
            one_m = 1.0 - acts[l] * acts[l]
            ddelta = one_m * de - 2.0 * acts[l] * da[l] * errs[l - 1]
            delta = one_m * errs[l - 1]
    return gw, gb
