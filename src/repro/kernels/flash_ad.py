"""Differentiable flash attention: the AD closure over the Pallas kernels.

``flash_mha`` is the training-path entry point (models/attention.py routes
``attend_full`` / ``encoder_attend`` here under ``cfg.use_flash_attention``).
It must compose with every transform the HF optimizer applies to the loss:

  * ``jax.value_and_grad``         — the outer-step gradient (Alg. 2 line 3),
  * ``jax.linearize`` + ``jax.linear_transpose`` — the curvature engine's
    Gauss-Newton product (J·v / Jᵀ·u, core/curvature.py::_gnvp_once),
  * ``jax.linearize(jax.grad(f))`` — the exact-Hessian product
    (forward-over-reverse, every ``curvature_mode``),
  * plain evaluation — the Armijo line search and serving prefill.

**First-order structure.** ``flash_mha`` is a ``jax.custom_jvp`` function
whose tangent rule is an extra flash pass with the saved logsumexp: the
Pallas JVP kernel computes ȯ = Σ_j P_ij(Ṡ_ij v_j + v̇_j) − t ∘ o blockwise,
and it is wired through ``jax.custom_derivatives.linear_call`` so that
*transposing* the tangent (what ``jax.grad`` and ``jax.linear_transpose``
do) lands on the Pallas backward kernels (dQ pass + dK/dV pass). Reverse
mode therefore saves only (q, k, v, o, lse) — O(S) residuals instead of the
O(S²) logits ``_sdpa`` materializes — and the gradient, the line search and
the whole Gauss-Newton Krylov loop run on Pallas kernels.

**Second-order structure.** Exact-Hessian products are forward-over-reverse:
``jax.linearize(jax.grad(loss))`` must forward-differentiate the *transposed*
tangent computation. No custom-transpose mechanism survives that —
``linear_call`` has no JVP rule, ``custom_vjp`` forbids forward mode
outright, and a scan emitted from inside a custom_jvp rule never acquires
the linearity annotations ``lax.scan``'s transpose rule requires (scan
transposition only works on scans that went through scan's *own* jvp rule).
Pallas closure at second order would mean flash double-backward kernels.
Instead, the curvature engine brackets its exact-Hessian operator builds in
``second_order_tangents()``; under that context the entry point swaps the
kernel for ``_chunked_attention`` — a plain-jnp attention chunked over
*query blocks* (a ``jax.checkpoint``-ed ``lax.scan``; K/V are broadcast
consts, per-block outputs are stacked ys, there is no sequence-sized carry).
Being ordinary jnp, JAX derives its gradient, its JVP, and the JVP of its
gradient by standard rules, and remat keeps every direction at O(S·blk)
memory — the (S, S) logits are never materialized, which is exactly what
the Krylov inner loop pays K times per outer step. The routing cannot be
inferred from trace state (``lax.scan``'s jvp rule re-traces bodies with
fresh tracers, hiding any outer transform), so it is explicit and
trace-time: the flag is read when the loss is *traced*, which is when the
engine builds its operators. Misrouting fails loudly: the first-order
entry's nested-forward rule raises with a pointer to the context manager.

Non-block-aligned sequences are padded to the 128-lane tile with the key
tail masked via ``valid_len`` and the output sliced back — the pad/slice is
ordinary jnp, so it is transparent to all of the above; padded query rows
are discarded by the slice and their tangents/cotangents are exact zeros.

One more routing consequence: ``jax.vmap`` over a cached linear map
containing the first-order tangent (core/blocks.py's s-step block
products) has no batching rule for ``linear_call``, so ``hf_step`` builds
the Gauss-Newton operator under ``second_order_tangents()`` whenever
``sstep_s > 1`` — the AD-closed form is plain jnp and vmaps fine (a no-op
for non-flash models). Exact-Hessian s-step operators are already built
under the context by the curvature engine.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from . import flash_attention as fa

NEG_INF = -1e30

_SECOND_ORDER_DEPTH = 0


@contextlib.contextmanager
def second_order_tangents():
    """Trace-time context: flash attention swaps its Pallas custom-AD rules
    for the AD-closed chunked-jnp form, so the traced computation supports
    forward-over-reverse (exact-Hessian products). Wrap the *trace* that
    builds the operator — core/curvature.py does this for every
    exact-Hessian mode."""
    global _SECOND_ORDER_DEPTH
    _SECOND_ORDER_DEPTH += 1
    try:
        yield
    finally:
        _SECOND_ORDER_DEPTH -= 1


def second_order_active() -> bool:
    return _SECOND_ORDER_DEPTH > 0


# --------------------------------------------------- shared AD-pass impls --
def flash_bwd_passes(q, k, v, o, lse, do, **kkw):
    """The attention VJP from the stored lse: Δ precompute, the Pallas dQ
    pass, the Pallas dK/dV pass, and the GQA group-sum (f32 partials).
    The single implementation behind both the linear_call transpose (what
    jax.grad executes) and the public ops.flash_attention_bwd wrapper the
    kernel tests pin — one copy, no drift. Head-major operands:
    q/o/do (B,H,Sq,hd), k/v (B,KV,Sk,hd), lse (B,H,Sq)."""
    delta = jnp.einsum("bhsd,bhsd->bhs", o.astype(jnp.float32),
                       do.astype(jnp.float32))
    dq = fa.flash_attention_dq(q, k, v, do, lse, delta, **kkw)
    dkh, dvh = fa.flash_attention_dkv(q, k, v, do, lse, delta, **kkw)
    B, H, _, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    dk = dkh.reshape(B, KV, G, Sk, hd).sum(2)
    dv = dvh.reshape(B, KV, G, Sk, hd).sum(2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def flash_jvp_pass(q, k, v, o, lse, qt, kt, vt, **kkw):
    """The attention JVP from the stored lse: the Pallas tangent pass plus
    the ȯ = g − t ∘ o finish (and l̇se = t). Single implementation behind
    the linear_call tangent and ops.flash_attention_jvp (head-major
    operands, as flash_bwd_passes)."""
    g, t = fa.flash_attention_jvp(q, k, v, qt, kt, vt, lse, **kkw)
    ot = g - t[..., None] * o.astype(jnp.float32)
    return ot.astype(o.dtype), t


# ----------------------------------------------- second-order (jnp) entry --
@jax.named_scope("chunked_attention")
def _chunked_attention(q, k, v, bias=None, *, causal, window, scale,
                       valid_len, blk):
    """Attention as a checkpointed scan over query blocks — the AD-closed
    form the exact-Hessian engine traces through.

    Each step computes softmax(q_blk Kᵀ)V for one (blk, Sk) tile: peak
    memory O(Sk·blk), never the (Sq, Sk) logits. K/V enter as (nonlinear)
    scan consts and the per-block outputs are stacked ys, so ``lax.scan``'s
    jvp rule gives the tangent scan correct linearity annotations — the
    structure every further transform (transpose, jvp-of-transpose)
    composes with by construction. ``jax.checkpoint`` on the body keeps the
    same O(Sk·blk) bound for all of them (P tiles are recomputed, not
    stored). ``bias``: optional (B|1, Sq, Sk) additive logit bias, sliced
    per query block (constant — differentiation passes it through as a
    zero-tangent const). Head-major operands, as the kernels take them.
    """
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    blk = min(blk, S)
    nb = S // blk
    f32 = jnp.float32
    qs = q.reshape(B, KV, G, nb, blk, hd).transpose(3, 0, 1, 2, 4, 5)
    if bias is not None:
        bias = jnp.broadcast_to(bias, (B, S, T))
        bias = bias.reshape(B, nb, blk, T).transpose(1, 0, 2, 3)
    else:
        bias = jnp.zeros((nb, 1, 1, 1), f32)

    def body(_, x):
        qb, bb, i0 = x                              # qb: (B, KV, G, blk, hd)
        s = jnp.einsum("bkgsh,bkth->bkgst", qb, k,
                       preferred_element_type=f32) * scale
        s = s + bb[:, None, None]
        mask = fa.position_mask(i0 + jnp.arange(blk)[:, None],
                                jnp.arange(T)[None, :], causal=causal,
                                window=window, valid_len=valid_len)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_safe), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        ob = jnp.einsum("bkgst,bkth->bkgsh", p / jnp.where(l <= 0.0, 1.0, l),
                        v, preferred_element_type=f32)
        return None, ob.reshape(B, H, blk, hd).astype(q.dtype)

    _, ys = jax.lax.scan(jax.checkpoint(body), None,
                         (qs, bias, jnp.arange(nb) * blk))
    return ys.transpose(1, 2, 0, 3, 4).reshape(B, H, S, hd)


# -------------------------------------------------------- per-config entry --
@functools.lru_cache(maxsize=None)
def _fa_entry(causal, window, scale, blk_q, blk_k, interpret, valid_len,
              second_order, has_bias=False):
    """Build (and cache) the differentiable attention callable for one
    static configuration. ``second_order`` is part of the cache key on
    purpose: the two rule sets must be distinct function objects so no
    jit/trace cache can alias them across contexts. ``has_bias`` entries
    take a fourth (B|1, Sq, Sk) f32 additive-bias operand — a constant
    w.r.t. differentiation (its tangent is discarded; masks carry no
    gradient), but a traced residual of every AD pass."""
    kkw = dict(causal=causal, window=window, valid_len=valid_len,
               scale=scale, blk_q=blk_q, blk_k=blk_k, interpret=interpret)

    if second_order:
        chunked = functools.partial(
            _chunked_attention, causal=causal, window=window, scale=scale,
            valid_len=valid_len, blk=blk_k)
        if has_bias:
            return lambda q, k, v, bias: chunked(q, k, v, bias)
        return chunked

    @jax.custom_jvp
    def fwd_res(q, k, v, bias=None):
        return fa.flash_attention_fwd(q, k, v, bias=bias, **kkw)

    @fwd_res.defjvp
    def fwd_res_jvp(primals, tangents):
        # Fires only when the primal forward is itself forward-differentiated
        # — i.e. forward-over-reverse reached the first-order entry. The
        # Pallas kernels cannot close that order; fail with the remedy.
        raise NotImplementedError(
            "flash attention: exact-Hessian (forward-over-reverse) traces "
            "must be built under kernels.ops.second_order_tangents() — the "
            "curvature engine does this; wrap any hand-rolled "
            "jvp-of-grad the same way.")

    def _tan(res, lin):
        # JVP flash pass (Pallas): linear in (q̇, k̇, v̇) given residuals.
        q, k, v, o, lse, bias = res
        return flash_jvp_pass(q, k, v, o, lse, *lin, bias=bias, **kkw)[0]

    def _tan_transpose(res, ct):
        # Transpose of _tan == the attention VJP: Pallas dQ + dK/dV passes
        # (this is what jax.grad / jax.linear_transpose execute).
        q, k, v, o, lse, bias = res
        return flash_bwd_passes(q, k, v, o, lse, ct, bias=bias, **kkw)

    if has_bias:
        @jax.custom_jvp
        def fa_o(q, k, v, bias):
            return fwd_res(q, k, v, bias)[0]

        @fa_o.defjvp
        def fa_o_jvp(primals, tangents):
            q, k, v, bias = primals
            o, lse = fwd_res(q, k, v, bias)
            # the bias tangent is dropped: masks are constants of the model
            ot = jax.custom_derivatives.linear_call(
                _tan, _tan_transpose, (q, k, v, o, lse, bias),
                tuple(tangents[:3]))
            return o, ot
    else:
        @jax.custom_jvp
        def fa_o(q, k, v):
            return fwd_res(q, k, v)[0]

        @fa_o.defjvp
        def fa_o_jvp(primals, tangents):
            q, k, v = primals
            o, lse = fwd_res(q, k, v)
            ot = jax.custom_derivatives.linear_call(
                _tan, _tan_transpose, (q, k, v, o, lse, None),
                tuple(tangents))
            return o, ot

    return jax.jit(fa_o)


# ------------------------------------------------------------ public entry --
def flash_mha(q, k, v, *, causal=True, window=None, scale=None,
              blk_q=128, blk_k=128, interpret=False, bias=None):
    """Differentiable flash attention with pad-and-mask block alignment.

    q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd) -> (B,Sq,H,hd). Query and key lengths
    may differ (cross-attention). When a length is not a multiple of the
    kernel block, that side is zero-padded to the next 128 multiple, the
    padded key tail is masked inside the kernels (``valid_len``), padded
    query rows are sliced back off (their tangents/cotangents are exact
    zeros). ``bias``: optional (B|1, Sq, Sk) f32 additive logit bias — the
    explicit-mask route (0 attendable / -1e30 dropped); it is treated as a
    constant under differentiation. The rule set (Pallas first-order vs
    AD-closed chunked-jnp) is picked by ``second_order_tangents()`` at trace
    time; see module docstring.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = float(scale if scale is not None else 1.0 / (hd ** 0.5))
    # Strict 128-tile contract: any length that is not a 128 multiple is
    # padded (including < 128) — sub-128 blocks would hand the TPU lane
    # dimension non-aligned logits/LSE tiles. 128-multiple lengths run
    # unpadded with the caller's block sizes.
    Sqp = -(-Sq // 128) * 128
    Skp = -(-Sk // 128) * 128
    valid_len = Sk if Skp != Sk else None
    entry = _fa_entry(causal, window, scale, blk_q, blk_k, bool(interpret),
                      valid_len, second_order_active(), bias is not None)
    # head-major for the kernels: (B, S, H, hd) -> (B, H, S, hd), padded
    q = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    kpad = ((0, 0), (0, 0), (0, Skp - Sk), (0, 0))
    k = jnp.pad(k.transpose(0, 2, 1, 3), kpad)
    v = jnp.pad(v.transpose(0, 2, 1, 3), kpad)
    if bias is not None:
        bias = jnp.pad(bias.astype(jnp.float32),
                       ((0, 0), (0, Sqp - Sq), (0, Skp - Sk)),
                       constant_values=NEG_INF)
        o = entry(q, k, v, bias)
    else:
        o = entry(q, k, v)
    return o[:, :, :Sq].transpose(0, 2, 1, 3)
