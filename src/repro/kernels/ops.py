"""Jit'd public wrappers for the Pallas kernels.

Attention (the training/serving hot path — see EXPERIMENTS.md §Perf pair F):

  * ``flash_attention``     — fully differentiable flash attention
                              (kernels/flash_ad.py: custom_jvp + linear_call
                              over the forward/backward/JVP kernels; padding
                              for non-block-aligned S),
  * ``flash_attention_fwd`` / ``flash_attention_bwd`` /
    ``flash_attention_jvp`` — the raw (non-differentiable) kernel passes,
  * ``second_order_tangents`` — trace-time context for exact-Hessian
                              (forward-over-reverse) traces, re-exported
                              from flash_ad for the curvature engine.

Decode (the serving hot path — see EXPERIMENTS.md §Perf pair H):

  * ``flash_decode``        — split-K single-query decode over a dense
                              rolling KV cache (kernels/flash_decode.py);
                              ``return_stats`` exposes the (o, m, l)
                              partials contract models/decode_sharded.py
                              merges across shards,
  * ``flash_decode_paged``  — the same kernel over the shared page pool
                              (models/kv_paged.py) with a scalar-prefetched
                              page table — no dense per-sequence gather,
  * ``decode_bias`` / ``paged_bias`` — the ONE definition of decode-mask
                              semantics (rolling-slot validity, ragged t,
                              sliding window, unmapped pages), shared by
                              the kernels, the jnp oracles, and `_sdpa`.

The remainder are the execution layer of the *flat* Krylov vector backend
(``core.krylov.FlatVectorBackend``): the solvers in ``core/solvers.py``
ravel their iterates into flat f32 buffers once per solve and run every
axpy/dot recurrence through these fusions —

  * ``bicgstab_x_update``     — y + α·u + γ·v  (Bi-CG-STAB x and p updates),
  * ``bicgstab_residual_dots``— r = s − γ·t fused with ⟨r,r0*⟩ and ⟨r,r⟩
                                (also the CG residual update + ‖r‖²),
  * ``dot2``                  — ⟨u,v⟩, ⟨v,v⟩ in one pass (curvature probes,
                                Bi-CG-STAB ω, CG α denominators),
  * ``gram_block``            — the (s_u × s_v) Gram matrix UVᵀ of two stacked
                                vector blocks in one pass (the s-step solvers'
                                all-dots-for-s-iterations reduction —
                                core/sstep.py via the Krylov block backend).

Each fusion removes whole HBM passes over model-sized vectors relative to
the per-leaf pytree path (see cg_fused.py for the traffic accounting) — the
flat backend wins when Krylov state is per-chip replicated (pure data
parallelism) and the inner loop is bandwidth-bound. The pytree ("tree")
backend keeps per-tensor shardings instead and wins when params are sharded
under pjit. ``benchmarks/kernels_bench.py`` compares both end-to-end.

Curvature (the Krylov solve's operator, see kernels/mlp_hvp.py):

  * ``mlp_hvp``               — the exact Hessian-vector product of a tanh
                                MLP with a softmax cross-entropy head in one
                                pass over row tiles, from the primal pass's
                                residuals (models/mlp.py routes the MLP's
                                exact curvature product here on the TPU).

``interpret=True`` runs the kernel bodies in Python on CPU (how this repo
validates them); on a real TPU pass interpret=False (default resolves from
the backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import cg_fused, flash_ad, flash_attention as fa, flash_decode as fd
from . import mlp_hvp as mh
from .flash_ad import second_order_tangents  # re-export (curvature engine)
from .flash_decode import decode_bias, paged_bias  # re-export (mask->bias)


def _default_interpret():
    return jax.default_backend() != "tpu"


def _hm(x):
    """(B, S, H, hd) <-> (B, H, S, hd): the model's layout to the kernels'
    head-major one (its own inverse)."""
    return x.transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, window=None, blk_q=128, blk_k=128,
                    interpret=None, bias=None):
    """Fully differentiable flash attention (training + serving path).

    Forward runs the Pallas online-softmax kernel (with the logsumexp
    residual); reverse mode transposes onto the Pallas dQ / dK+dV kernels;
    forward mode (``jax.linearize`` — the curvature engine's J·v) runs the
    Pallas JVP pass. Exact-Hessian (forward-over-reverse) traces must be
    bracketed in ``second_order_tangents()`` — see kernels/flash_ad.py.
    Non-block-aligned lengths are padded to the 128 tile, tail-masked and
    sliced; q and kv lengths may differ (cross-attention). ``bias``:
    optional (B|1, Sq, Sk) f32 additive logit bias — the explicit-mask
    route (constant under differentiation).
    """
    interpret = _default_interpret() if interpret is None else interpret
    return flash_ad.flash_mha(
        q, k, v, causal=causal, window=window, blk_q=blk_q, blk_k=blk_k,
        interpret=interpret, bias=bias,
    )


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "valid_len", "blk_q", "blk_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal=True, window=None, valid_len=None,
                        blk_q=128, blk_k=128, interpret=None, bias=None):
    """Raw forward kernel: (o, lse) with lse: (B,H,S) the per-row logsumexp
    residual the backward/JVP kernels consume (non-differentiable wrapper)."""
    interpret = _default_interpret() if interpret is None else interpret
    o, lse = fa.flash_attention_fwd(
        _hm(q), _hm(k), _hm(v), causal=causal, window=window,
        valid_len=valid_len, blk_q=blk_q, blk_k=blk_k, interpret=interpret,
        bias=bias)
    return _hm(o), lse


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "valid_len", "blk_q", "blk_k", "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=None,
                        valid_len=None, blk_q=128, blk_k=128, interpret=None,
                        bias=None):
    """Raw backward: (dq, dk, dv) from the stored lse — Δ precompute, the
    Pallas dQ pass, the Pallas dK/dV pass, and the GQA group-sum. Same
    implementation jax.grad executes (flash_ad.flash_bwd_passes)."""
    interpret = _default_interpret() if interpret is None else interpret
    grads = flash_ad.flash_bwd_passes(
        _hm(q), _hm(k), _hm(v), _hm(o), lse, _hm(do), causal=causal,
        window=window, bias=bias, valid_len=valid_len, blk_q=blk_q,
        blk_k=blk_k, interpret=interpret)
    return tuple(_hm(g) for g in grads)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "valid_len", "blk_q", "blk_k", "interpret"))
def flash_attention_jvp(q, k, v, o, lse, qt, kt, vt, *, causal=True,
                        window=None, valid_len=None, blk_q=128, blk_k=128,
                        interpret=None, bias=None):
    """Raw forward-mode tangent: (ȯ, l̇se) via the Pallas JVP pass (two extra
    block matmuls per tile: Q̇Kᵀ + QK̇ᵀ against the recomputed P). Same
    implementation jax.linearize executes (flash_ad.flash_jvp_pass)."""
    interpret = _default_interpret() if interpret is None else interpret
    ot, lset = flash_ad.flash_jvp_pass(
        _hm(q), _hm(k), _hm(v), _hm(o), lse, _hm(qt), _hm(kt), _hm(vt),
        causal=causal, window=window, bias=bias, valid_len=valid_len,
        blk_q=blk_q, blk_k=blk_k, interpret=interpret)
    return _hm(ot), lset


@functools.partial(jax.jit, static_argnames=(
    "scale", "blk_k", "n_splits", "interpret", "return_stats"))
def flash_decode(q, k, v, bias, *, scale=None, blk_k=128, n_splits=8,
                 interpret=None, return_stats=False):
    """Split-K flash decode over a dense rolling cache (serving hot path).

    q: (B,H,hd) one query row per sequence; k/v: (B,W,KV,hd); bias: (B|1,W)
    additive mask row from ``decode_bias`` (rolling-slot validity, ragged
    per-sequence t, sliding window). The grid parallelizes over KV blocks;
    partials merge with the logsumexp combine (kernels/flash_decode.py).
    ``return_stats`` additionally returns global (m, l): (B,H) — the
    contract models/decode_sharded.py uses to merge across shards.
    """
    interpret = _default_interpret() if interpret is None else interpret
    return fd.flash_decode(
        q, k, v, bias, scale=scale, blk_k=blk_k, n_splits=n_splits,
        interpret=interpret, return_stats=return_stats)


@functools.partial(jax.jit, static_argnames=(
    "scale", "interpret", "return_stats"))
def flash_decode_paged(q, k_pool, v_pool, page_table, bias, *, scale=None,
                       interpret=None, return_stats=False):
    """Split-K flash decode over the shared page pool (models/kv_paged.py).

    The page table is scalar-prefetched so the kernel's K/V index maps
    gather physical pages directly — no dense per-sequence copy. bias from
    ``paged_bias`` masks the beyond-length tail, sliding window, and
    unmapped pages.
    """
    interpret = _default_interpret() if interpret is None else interpret
    return fd.flash_decode_paged(
        q, k_pool, v_pool, page_table, bias, scale=scale,
        interpret=interpret, return_stats=return_stats)


def _pad_flat(x, block):
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x, n


@functools.partial(jax.jit, static_argnames=("interpret",))
def bicgstab_x_update(x, p, s, alpha, gamma, *, interpret=None):
    """x + alpha*p + gamma*s  (flat f32 vectors)."""
    interpret = _default_interpret() if interpret is None else interpret
    xp, n = _pad_flat(x, cg_fused.BLOCK)
    pp, _ = _pad_flat(p, cg_fused.BLOCK)
    sp, _ = _pad_flat(s, cg_fused.BLOCK)
    return cg_fused.x_update(xp, pp, sp, alpha, gamma, interpret=interpret)[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bicgstab_residual_dots(s, As, r0s, gamma, *, interpret=None):
    """r = s - gamma*As; returns (r, <r,r0s>, <r,r>)."""
    interpret = _default_interpret() if interpret is None else interpret
    sp, n = _pad_flat(s, cg_fused.BLOCK)
    Ap, _ = _pad_flat(As, cg_fused.BLOCK)
    rp, _ = _pad_flat(r0s, cg_fused.BLOCK)
    r, d1, d2 = cg_fused.residual_dots(sp, Ap, rp, gamma, interpret=interpret)
    return r[:n], jnp.sum(d1), jnp.sum(d2)


def _pad_block_rows(M, block, row_tile=8):
    """Pad a (s, n) stack to (s_pad, n_pad): columns to a kernel-block
    multiple, rows to the f32 sublane tile (zero rows/columns contribute
    zero to every Gram entry)."""
    s, n = M.shape
    pad_c = (-n) % block
    pad_r = (-s) % row_tile
    if pad_c or pad_r:
        M = jnp.pad(M, ((0, pad_r), (0, pad_c)))
    return M, s


@functools.partial(jax.jit, static_argnames=("interpret",))
def gram_block(U, V, *, interpret=None):
    """Gram matrix U @ Vᵀ of two stacked flat f32 vector blocks.

    ``U``: (s_u, n), ``V``: (s_v, n) → (s_u, s_v) with every entry ⟨u_i, v_j⟩
    accumulated in one pass over the data (per-column-block partials from the
    Pallas kernel, reduced here). This is the flat backend's ``gram`` — the
    single reduction an s-step cycle issues in place of per-iteration dots.
    """
    interpret = _default_interpret() if interpret is None else interpret
    Up, su = _pad_block_rows(U, cg_fused.BLOCK_GRAM)
    Vp, sv = _pad_block_rows(V, cg_fused.BLOCK_GRAM)
    parts = cg_fused.dots_block(Up, Vp, interpret=interpret)
    return jnp.sum(parts, axis=0)[:su, :sv]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dot2(u, v, *, interpret=None):
    """(<u,v>, <v,v>)."""
    interpret = _default_interpret() if interpret is None else interpret
    up, _ = _pad_flat(u, cg_fused.BLOCK)
    vp, _ = _pad_flat(v, cg_fused.BLOCK)
    d1, d2 = cg_fused.dot2(up, vp, interpret=interpret)
    return jnp.sum(d1), jnp.sum(d2)


def _round_up(n, m):
    return -(-n // m) * m


def _pad_to(a, shape):
    pads = [(0, t - s) for s, t in zip(a.shape, shape)]
    return jnp.pad(a, pads) if any(hi for _, hi in pads) else a


def mlp_hvp(acts, errs, p, y, weights, dweights, dbiases, *,
            block_rows=mh.BLOCK_ROWS, interpret=None):
    """Exact Hessian-vector product of a tanh MLP with a mean softmax
    cross-entropy head over the rows of ``acts[0]``.

    ``acts``: the layers' inputs ``x, a_1 … a_{L-1}`` (``[n, d]`` f32);
    ``errs``: ``∂loss/∂a_l`` for ``l = 1 … L-1``; ``p``: the softmax
    ``[n, classes]``; ``y``: ``[n]`` integer labels; ``weights``: the L
    ``[d_in, d_out]`` matrices; ``dweights``/``dbiases``: the direction.
    Returns the product's ``(d∇W list, d∇b list)`` in f32.

    Widths are zero-padded to 128 lanes here (the padded columns add exact
    zeros); under ``jax.linearize`` the residuals' padding and casts run
    once, in the primal pass, and each product pads only the direction.
    """
    interpret = _default_interpret() if interpret is None else interpret
    n = acts[0].shape[0]
    dims = [acts[0].shape[1]] + [w.shape[1] for w in weights]
    pad = [_round_up(d, 128) for d in dims]
    acts = [_pad_to(a.astype(jnp.float32), (n, d))
            for a, d in zip(acts, pad)]
    errs = [_pad_to(e.astype(jnp.float32), (n, d))
            for e, d in zip(errs, pad[1:])]
    p = _pad_to(p.astype(jnp.float32), (n, pad[-1]))
    mats = lambda ws: [_pad_to(w.astype(jnp.bfloat16), (a, b))
                       for w, a, b in zip(ws, pad, pad[1:])]
    dbs = [_pad_to(b.astype(jnp.float32).reshape(1, -1), (1, d))
           for b, d in zip(dbiases, pad[1:])]
    gw, gb = mh.mlp_hvp(acts, errs, p, y.astype(jnp.int32).reshape(n, 1),
                        mats(weights), mats(dweights), dbs,
                        block_rows=block_rows, interpret=interpret)
    return ([g[:a, :b] for g, a, b in zip(gw, dims, dims[1:])],
            [g[0, :b] for g, b in zip(gb, dims[1:])])
