"""Flash attention (forward + backward + JVP) as Pallas TPU kernels.

Online-softmax blockwise attention: every kernel runs on a 4-D grid whose
innermost dimension is the reduction axis and keeps its accumulators in VMEM
scratch across that axis. Causal and sliding-window masks are applied inside
the block; fully-masked key blocks contribute nothing (the m/l recurrence is
a no-op for -inf rows). GQA is handled in the index maps (kv head =
q head // group). ``valid_len`` masks a zero-padded key tail so
non-block-aligned sequences can be padded to the 128 lane tile and sliced
(see kernels.flash_ad.flash_mha). Query and key lengths may differ
(cross-attention), and every kernel takes an optional (B|1, Sq, Sk) f32
additive logit ``bias`` operand — the pad-and-mask route for explicit
attention masks (0 attendable / -1e30 dropped; batch-1 biases broadcast in
the index map without a materialized copy).

Kernels (S = q length, hd = head dim):

  * ``_fa_kernel``      — forward; emits O and the per-row logsumexp
                          LSE_i = m_i + log l_i, the residual every other
                          kernel uses to recompute P = exp(S·scale − LSE)
                          blockwise instead of storing the (S, S) weights.
  * ``_fa_dq_kernel``   — backward dQ pass: grid (B, H, q_blocks, k_blocks),
                          dQ_i = scale · Σ_j P_ij (dP_ij − Δ_i) K_j with
                          dP = dO Vᵀ and Δ = rowsum(dO ∘ O) precomputed.
  * ``_fa_dkv_kernel``  — backward dK/dV pass: grid (B, H, k_blocks,
                          q_blocks) (reduction over q blocks), emitting
                          per-q-head dK/dV; the GQA group-sum happens in the
                          caller (kernels.ops.flash_attention_bwd).
  * ``_fa_jvp_kernel``  — forward-mode tangent pass: with Ṡ = scale·(Q̇Kᵀ +
                          QK̇ᵀ), accumulates G_i = Σ_j P_ij (Ṡ_ij V_j + V̇_j)
                          and t_i = Σ_j P_ij Ṡ_ij; the caller finishes
                          Ȯ = G − t ∘ O (and L̇SE = t). This is the extra
                          flash pass that makes the kernel usable under
                          ``jax.linearize`` (the curvature engine's J·v).

The kernels take head-major operands — q (B, H, Sq, hd), k/v (B, KV, Sk,
hd) — so BlockSpecs stage (blk_q x hd) query tiles and (blk_k x hd)
key/value tiles into VMEM with the head axis squeezed out of the two minor
dimensions, as Mosaic requires; the MXU sees (blk_q x hd) @ (hd x blk_k)
matmuls with hardware-aligned tiles (blk_* multiples of 128 for f32/bf16).
LSE/Δ/t ride as (B, H, 1, S) rows with (1, blk_q) blocks.
``kernels.flash_ad`` moves the model's (B, S, H, hd) activations into this
layout once per attention call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def position_mask(q_pos, k_pos, *, causal, window, valid_len):
    """Broadcasted attention mask from query/key position arrays — the ONE
    definition of the causal/sliding-window/valid-length semantics, shared
    by every Pallas kernel here and by the chunked-jnp second-order route
    (kernels/flash_ad.py), so the two routes cannot drift. The pure-jnp
    oracle (kernels/ref.py) keeps an independent copy on purpose: it is the
    ground truth these semantics are tested against."""
    mask = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window is not None:
        mask = jnp.logical_and(mask, k_pos > q_pos - window)
    if valid_len is not None:
        mask = jnp.logical_and(mask, k_pos < valid_len)
    return mask


def _block_mask(qi, ki, blk_q, blk_k, *, causal, window, valid_len):
    """(blk_q, blk_k) boolean mask for the (qi, ki) grid cell."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return position_mask(q_pos, k_pos, causal=causal, window=window,
                         valid_len=valid_len)


def _fa_kernel(q_ref, k_ref, v_ref, *refs,
               scale, causal, window, valid_len, blk_q, blk_k, n_k_blocks,
               has_bias=False):
    if has_bias:
        bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                                   # (blk_q, hd)
    k = k_ref[...]                                   # (blk_k, hd)
    v = v_ref[...]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                               # (blk_q, blk_k)
    if has_bias:
        # additive f32 bias tile (explicit masks: 0 attend / NEG_INF drop);
        # masked entries underflow exp() to exact 0 below
        logits = logits + bias_ref[0]

    mask = _block_mask(qi, ki, blk_q, blk_k, causal=causal, window=window,
                       valid_len=valid_len)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]                                     # (blk_q, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(jnp.where(mask, logits - m_safe, NEG_INF))  # (blk_q, blk_k)
    alpha = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev - m_safe))
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        norm = jnp.where(l_new <= 0.0, 1.0, l_new)
        o_ref[...] = (acc / norm).astype(o_ref.dtype)
        # per-row logsumexp residual; fully-masked rows get lse = 0 and the
        # downstream kernels mask their P entries explicitly anyway.
        m_fin = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        lse_ref[0] = (m_fin + jnp.log(norm))[:, 0]


def _recompute_p(q, k, lse, qi, ki, *, scale, causal, window, valid_len,
                 blk_q, blk_k, bias=None):
    """P block from the stored LSE: P_ij = exp(scale·q_i·k_j + bias − lse_i)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias
    mask = _block_mask(qi, ki, blk_q, blk_k, causal=causal, window=window,
                       valid_len=valid_len)
    return jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0), mask


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                  scale, causal, window, valid_len, blk_q, blk_k,
                  n_k_blocks, has_bias=False):
    if has_bias:
        bias_ref, dq_ref, acc_scr = refs
    else:
        dq_ref, acc_scr = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[0]
    delta = delta_ref[0]

    p, _ = _recompute_p(q, k, lse, qi, ki, scale=scale, causal=causal,
                        window=window, valid_len=valid_len,
                        blk_q=blk_q, blk_k=blk_k,
                        bias=bias_ref[0] if has_bias else None)
    dp = jax.lax.dot_general(                               # dO @ Vᵀ
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta[:, None])                          # (blk_q, blk_k)
    acc_scr[...] += jax.lax.dot_general(                    # dS @ K
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   scale, causal, window, valid_len, blk_q, blk_k,
                   n_q_blocks, has_bias=False):
    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    # grid (B, H, k_blocks, q_blocks): reduction over q blocks (innermost)
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[0]
    delta = delta_ref[0]

    p, _ = _recompute_p(q, k, lse, qi, ki, scale=scale, causal=causal,
                        window=window, valid_len=valid_len,
                        blk_q=blk_q, blk_k=blk_k,
                        bias=bias_ref[0] if has_bias else None)
    dv_scr[...] += jax.lax.dot_general(                     # Pᵀ @ dO
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta[:, None])
    dk_scr[...] += jax.lax.dot_general(                     # dSᵀ @ Q
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(qi == n_q_blocks - 1)
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _fa_jvp_kernel(q_ref, k_ref, v_ref, qt_ref, kt_ref, vt_ref, lse_ref,
                   *refs, scale, causal, window, valid_len, blk_q, blk_k,
                   n_k_blocks, has_bias=False):
    if has_bias:
        bias_ref, g_ref, t_ref, g_scr, t_scr = refs
    else:
        g_ref, t_ref, g_scr, t_scr = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)
        t_scr[...] = jnp.zeros_like(t_scr)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    qt = qt_ref[...]
    kt = kt_ref[...]
    vt = vt_ref[...]
    lse = lse_ref[0]

    p, mask = _recompute_p(q, k, lse, qi, ki, scale=scale, causal=causal,
                           window=window, valid_len=valid_len,
                           blk_q=blk_q, blk_k=blk_k,
                           bias=bias_ref[0] if has_bias else None)
    st = (jax.lax.dot_general(                              # Q̇ Kᵀ + Q K̇ᵀ
        qt, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        q, kt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )) * scale
    r = p * jnp.where(mask, st, 0.0)                        # P ∘ Ṡ
    g_scr[...] += jax.lax.dot_general(
        r.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    t_scr[...] += jnp.sum(r, axis=1, keepdims=True)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        g_ref[...] = g_scr[...].astype(g_ref.dtype)
        t_ref[0] = t_scr[:, 0]


# --------------------------------------------------------------- wrappers --
# Layout: q (B, H, Sq, hd), k/v (B, KV, Sk, hd), so every tile's two minor
# dimensions are (blk, hd) — what Mosaic tiles ((8, 128) or the full dim).
# Per-row statistics (lse, Δ, t) are (B, H, Sq) to callers and (B, H, 1, Sq)
# to the kernels: a (1, blk_q) row block whose second-minor dim is the whole
# (unit) axis.
def _shapes(q, k, blk_q, blk_k):
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    assert Sq % blk_q == 0 and Sk % blk_k == 0, (Sq, Sk, blk_q, blk_k)
    return B, Sq, Sk, H, hd, KV, G, blk_q, blk_k, Sq // blk_q, Sk // blk_k


def _resolve_scale(scale, hd):
    return float(scale if scale is not None else 1.0 / (hd ** 0.5))


def _q_spec(blk_q, hd, transposed_grid=False):
    """(blk_q, hd) tile of a (B, H, Sq, hd) operand. ``transposed_grid``:
    the dK/dV grid is (B, H, k, q)."""
    if transposed_grid:
        return pl.BlockSpec((None, None, blk_q, hd), lambda b, h, j, i: (b, h, i, 0))
    return pl.BlockSpec((None, None, blk_q, hd), lambda b, h, i, j: (b, h, i, 0))


def _kv_spec(blk_k, hd, G, transposed_grid=False):
    """(blk_k, hd) tile of a (B, KV, Sk, hd) operand; query head h reads kv
    head h // G (GQA)."""
    if transposed_grid:
        return pl.BlockSpec((None, None, blk_k, hd),
                            lambda b, h, j, i: (b, h // G, j, 0))
    return pl.BlockSpec((None, None, blk_k, hd),
                        lambda b, h, i, j: (b, h // G, j, 0))


def _row_spec(blk_q, transposed_grid=False):
    """(1, blk_q) block of a (B, H, 1, Sq) per-row statistic."""
    if transposed_grid:
        return pl.BlockSpec((None, None, 1, blk_q), lambda b, h, j, i: (b, h, 0, i))
    return pl.BlockSpec((None, None, 1, blk_q), lambda b, h, i, j: (b, h, 0, i))


def _bias_spec(bias, blk_q, blk_k, transposed_grid=False):
    """BlockSpec for the optional (Bb, Sq, Sk) f32 additive-bias operand.
    Bb == 1 broadcasts over the batch in the index map (no materialized
    copy)."""
    bb = bias.shape[0]
    if transposed_grid:
        return pl.BlockSpec((1, blk_q, blk_k),
                            lambda b, h, j, i: (b if bb > 1 else 0, i, j))
    return pl.BlockSpec((1, blk_q, blk_k),
                        lambda b, h, i, j: (b if bb > 1 else 0, i, j))


def _with_bias(in_specs, args, bias, blk_q, blk_k, transposed_grid=False):
    if bias is None:
        return in_specs, args
    return (in_specs + [_bias_spec(bias, blk_q, blk_k, transposed_grid)],
            args + (bias,))


def flash_attention_fwd(q, k, v, *, causal=True, window=None, valid_len=None,
                        scale=None, blk_q=128, blk_k=128, interpret=False,
                        bias=None):
    """q: (B,H,Sq,hd), k/v: (B,KV,Sk,hd) -> (o: (B,H,Sq,hd), lse: (B,H,Sq)).
    ``bias``: optional (B|1, Sq, Sk) f32 additive logit bias (explicit
    masks: 0 attend / NEG_INF drop)."""
    B, Sq, Sk, H, hd, KV, G, blk_q, blk_k, nq, nk = _shapes(q, k, blk_q, blk_k)
    scale = _resolve_scale(scale, hd)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        valid_len=valid_len, blk_q=blk_q, blk_k=blk_k, n_k_blocks=nk,
        has_bias=bias is not None,
    )
    in_specs, args = _with_bias(
        [_q_spec(blk_q, hd), _kv_spec(blk_k, hd, G), _kv_spec(blk_k, hd, G)],
        (q, k, v), bias, blk_q, blk_k)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=(_q_spec(blk_q, hd), _row_spec(blk_q)),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return o, lse[:, :, 0]


def flash_attention(q, k, v, *, causal=True, window=None, valid_len=None,
                    scale=None, blk_q=128, blk_k=128, interpret=False,
                    bias=None):
    """Forward only (serving path): q (B,H,Sq,hd), k/v (B,KV,Sk,hd) -> o."""
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, valid_len=valid_len,
        scale=scale, blk_q=blk_q, blk_k=blk_k, interpret=interpret, bias=bias,
    )[0]


def flash_attention_dq(q, k, v, do, lse, delta, *, causal=True, window=None,
                       valid_len=None, scale=None, blk_q=128, blk_k=128,
                       interpret=False, bias=None):
    """Backward dQ pass. lse/delta: (B,H,Sq). Returns dq (B,H,Sq,hd)."""
    B, Sq, Sk, H, hd, KV, G, blk_q, blk_k, nq, nk = _shapes(q, k, blk_q, blk_k)
    scale = _resolve_scale(scale, hd)
    kernel = functools.partial(
        _fa_dq_kernel, scale=scale, causal=causal, window=window,
        valid_len=valid_len, blk_q=blk_q, blk_k=blk_k, n_k_blocks=nk,
        has_bias=bias is not None,
    )
    in_specs, args = _with_bias(
        [_q_spec(blk_q, hd), _kv_spec(blk_k, hd, G), _kv_spec(blk_k, hd, G),
         _q_spec(blk_q, hd), _row_spec(blk_q), _row_spec(blk_q)],
        (q, k, v, do, lse[:, :, None], delta[:, :, None]), bias, blk_q, blk_k)
    return pl.pallas_call(
        kernel,
        name="flash_attention_dq",
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=_q_spec(blk_q, hd),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, hd), jnp.float32)],
        interpret=interpret,
    )(*args)


def flash_attention_dkv(q, k, v, do, lse, delta, *, causal=True, window=None,
                        valid_len=None, scale=None, blk_q=128, blk_k=128,
                        interpret=False, bias=None):
    """Backward dK/dV pass, per *query* head (the caller sums each GQA
    group). Returns (dk_h, dv_h): (B,H,Sk,hd)."""
    B, Sq, Sk, H, hd, KV, G, blk_q, blk_k, nq, nk = _shapes(q, k, blk_q, blk_k)
    scale = _resolve_scale(scale, hd)
    kernel = functools.partial(
        _fa_dkv_kernel, scale=scale, causal=causal, window=window,
        valid_len=valid_len, blk_q=blk_q, blk_k=blk_k, n_q_blocks=nq,
        has_bias=bias is not None,
    )
    in_specs, args = _with_bias(
        [_q_spec(blk_q, hd, True), _kv_spec(blk_k, hd, G, True),
         _kv_spec(blk_k, hd, G, True), _q_spec(blk_q, hd, True),
         _row_spec(blk_q, True), _row_spec(blk_q, True)],
        (q, k, v, do, lse[:, :, None], delta[:, :, None]), bias, blk_q, blk_k,
        transposed_grid=True)
    return pl.pallas_call(
        kernel,
        name="flash_attention_dkv",
        grid=(B, H, nk, nq),
        in_specs=in_specs,
        out_specs=(_kv_spec(blk_k, hd, 1, True), _kv_spec(blk_k, hd, 1, True)),
        out_shape=(
            # per-q-head partials stay f32 so the GQA group-sum outside the
            # kernel accumulates at full precision even for bf16 models
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk_k, hd), jnp.float32),
            pltpu.VMEM((blk_k, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*args)


def flash_attention_jvp(q, k, v, qt, kt, vt, lse, *, causal=True, window=None,
                        valid_len=None, scale=None, blk_q=128, blk_k=128,
                        interpret=False, bias=None):
    """Tangent pass: returns (g: (B,H,Sq,hd), t: (B,H,Sq)) with
    g_i = Σ_j P_ij (Ṡ_ij v_j + v̇_j) and t_i = Σ_j P_ij Ṡ_ij; the caller
    forms ȯ = g − t ∘ o (and l̇se = t)."""
    B, Sq, Sk, H, hd, KV, G, blk_q, blk_k, nq, nk = _shapes(q, k, blk_q, blk_k)
    scale = _resolve_scale(scale, hd)
    kernel = functools.partial(
        _fa_jvp_kernel, scale=scale, causal=causal, window=window,
        valid_len=valid_len, blk_q=blk_q, blk_k=blk_k, n_k_blocks=nk,
        has_bias=bias is not None,
    )
    in_specs, args = _with_bias(
        [_q_spec(blk_q, hd), _kv_spec(blk_k, hd, G), _kv_spec(blk_k, hd, G),
         _q_spec(blk_q, hd), _kv_spec(blk_k, hd, G), _kv_spec(blk_k, hd, G),
         _row_spec(blk_q)],
        (q, k, v, qt, kt, vt, lse[:, :, None]), bias, blk_q, blk_k)
    g, t = pl.pallas_call(
        kernel,
        name="flash_attention_jvp",
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=(_q_spec(blk_q, hd), _row_spec(blk_q)),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Sq, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk_q, hd), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return g, t[:, :, 0]
