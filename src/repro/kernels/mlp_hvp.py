"""The exact Hessian-vector product of a dense tanh MLP with a mean softmax
cross-entropy head, as one Pallas pass over row tiles.

The network is ``z_l = a_{l-1}·W_l + b_l``, ``a_l = tanh(z_l)`` for
``l < L``, logits ``z_L``, loss ``mean_i CE(softmax(z_L[i]), y[i])`` over
the ``B`` rows. Its primal backward pass leaves, per row, the activations
``a_0 = x, a_1 … a_{L-1}``, the gradients ``e_l = ∂loss/∂a_l`` and the
softmax ``p``; ``δ_L = (p − onehot(y))/B`` and ``δ_l = (1 − a_l²)⊙e_l``
are the pre-activation gradients. Pearlmutter's R-operator along a
parameter direction ``(dW, db)`` is then row-local:

  forward tangent   dz_l = da_{l-1}·W_l + a_{l-1}·dW_l + db_l   (da_0 = 0)
                    da_l = (1 − a_l²)⊙dz_l
  softmax-CE        dδ_L = (p⊙dz_L − p·⟨p, dz_L⟩)/B
  backward tangent  de_l = dδ_{l+1}·W_{l+1}ᵀ + δ_{l+1}·dW_{l+1}ᵀ
                    dδ_l = (1 − a_l²)⊙de_l − 2·a_l⊙da_l⊙e_l
  product           d∇W_l = da_{l-1}ᵀ·δ_l + a_{l-1}ᵀ·dδ_l,  d∇b_l = Σ_rows dδ_l

so one grid step reads one row tile of the residuals, keeps every tangent
of that tile in VMEM, and adds its share of ``d∇W``/``d∇b`` into f32
accumulators that stay resident over the sequential grid. The weights and
their tangents stay resident too. Under XLA the same product writes each
``[rows, classes]`` and ``[rows, width]`` tangent to HBM and reads it back
in the next fusion; at large curvature batches those passes set its time.

Numerics follow JAX's default matmul precision on the TPU: MXU operands in
bfloat16, accumulation in float32; all elementwise math in float32, and
the residuals are read as float32.

Layout (``ops.mlp_hvp`` pads): every width is a multiple of 128 lanes,
zero-padded, so padded columns contribute exact zeros; the rows need not
divide the tile (the last tile's rows past ``n_rows`` are masked to zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
VMEM_LIMIT = 100 * 1024 * 1024    # of the v5e's 128 MiB

_BF16 = jnp.bfloat16
_F32 = jnp.float32


def _mm(u, w):
    """u·w on the MXU: bf16 operands, f32 accumulation."""
    return jnp.dot(u.astype(_BF16), w, preferred_element_type=_F32)


def _mm_nt(u, w):
    """u·wᵀ."""
    return jax.lax.dot_general(u.astype(_BF16), w, (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def _mm_tn(u, v):
    """uᵀ·v, contracting the rows."""
    return jax.lax.dot_general(u.astype(_BF16), v.astype(_BF16),
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _kernel(*refs, n_layers, n_rows, block_rows, inv_b, mask_rows):
    L = n_layers
    refs = list(refs)
    take = lambda k: [refs.pop(0) for _ in range(k)]
    acts_r, errs_r = take(L), take(L - 1)
    (p_r,), (y_r,) = take(1), take(1)
    w_r, dw_r, db_r = take(L), take(L), take(L)
    gw_r, gb_r = take(L), take(L)

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for r in gw_r + gb_r:
            r[...] = jnp.zeros(r.shape, r.dtype)

    valid = None
    if mask_rows:
        rows = i * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, 1), 0)
        valid = rows < n_rows

    def load(ref, fill=0):
        v = ref[...]
        return v if valid is None else jnp.where(valid, v, fill)

    acts = [load(r) for r in acts_r]          # a_0 = x, a_1 … a_{L-1}
    errs = [None] + [load(r) for r in errs_r]  # e_l beside a_l
    p, y = load(p_r), load(y_r, -1)
    w = [r[...] for r in w_r]
    dw = [r[...] for r in dw_r]

    # forward tangent
    da = [None]
    dz = _mm(acts[0], dw[0]) + db_r[0][...]
    for l in range(1, L):
        d = (1.0 - acts[l] * acts[l]) * dz
        da.append(d)
        dz = _mm(d, w[l]) + _mm(acts[l], dw[l]) + db_r[l][...]

    # softmax cross-entropy: δ_L and its tangent
    classes = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    delta = (p - (classes == y).astype(_F32)) * inv_b
    s = jnp.sum(p * dz, axis=1, keepdims=True)
    ddelta = p * (dz - s) * inv_b

    # backward tangent and the product
    for l in reversed(range(L)):
        g = _mm_tn(acts[l], ddelta)
        if l:
            g = g + _mm_tn(da[l], delta)
        gw_r[l][...] += g
        gb_r[l][...] += jnp.sum(ddelta, axis=0, keepdims=True)
        if l:
            de = _mm_nt(ddelta, w[l]) + _mm_nt(delta, dw[l])
            one_m = 1.0 - acts[l] * acts[l]
            ddelta = one_m * de - 2.0 * acts[l] * da[l] * errs[l]
            delta = one_m * errs[l]


def mlp_hvp(acts, errs, p, y, w, dw, db, *, block_rows=BLOCK_ROWS,
            interpret=False):
    """One fused pass; operands already laid out (``ops.mlp_hvp``).

    ``acts``: L arrays ``[n, d_{l-1}]`` f32 (x, a_1 … a_{L-1});
    ``errs``: L−1 arrays ``[n, d_l]`` f32; ``p``: ``[n, d_L]`` f32;
    ``y``: ``[n, 1]`` int32; ``w``/``dw``: L arrays ``[d_{l-1}, d_l]``
    bf16; ``db``: L arrays ``[1, d_l]`` f32. Every ``d`` a multiple of 128.
    Returns (d∇W list f32, d∇b list ``[1, d_l]`` f32) of the mean over the
    ``n`` rows.
    """
    L = len(w)
    n = p.shape[0]
    tm = min(block_rows, -(-n // 8) * 8)
    nb = pl.cdiv(n, tm)
    rows = lambda d: pl.BlockSpec((tm, d), lambda i: (i, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0),
                                       pipeline_mode=pl.Buffered(1))
    in_specs = ([rows(a.shape[1]) for a in acts]
                + [rows(e.shape[1]) for e in errs]
                + [rows(p.shape[1]), rows(1)]
                + [whole(t.shape) for t in list(w) + list(dw) + list(db)])
    gw_shapes = [t.shape for t in w]
    gb_shapes = [t.shape for t in db]
    out_specs = [whole(s) for s in gw_shapes + gb_shapes]
    out_shape = [jax.ShapeDtypeStruct(s, _F32) for s in gw_shapes + gb_shapes]
    mm_flops = sum(a * b for a, b in gw_shapes)
    kernel = functools.partial(_kernel, n_layers=L, n_rows=n, block_rows=tm,
                               inv_b=1.0 / n, mask_rows=n % tm != 0)
    out = pl.pallas_call(
        kernel,
        name="mlp_hvp",
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * (6 * mm_flops - 4 * gw_shapes[0][0] * gw_shapes[0][1]),
            transcendentals=0,
            bytes_accessed=4 * n * (sum(a.shape[1] for a in acts)
                                    + sum(e.shape[1] for e in errs)
                                    + p.shape[1] + 1)),
        interpret=interpret,
    )(*acts, *errs, p, y, *w, *dw, *db)
    return out[:L], out[L:]
