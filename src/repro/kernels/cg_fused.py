"""Fused Bi-CG-STAB vector recurrences as Pallas TPU kernels.

The paper's inner loop streams ~N-element (model-sized) vectors through HBM;
on TPU these recurrences are pure bandwidth. Fusing the axpy chains with the
dot products they feed removes whole HBM passes:

  * ``x_update``:       x + α·p + γ·s                (3 reads 1 write, vs 4r/2w)
  * ``residual_dots``:  r = s − γ·As; ⟨r,r0*⟩; ⟨r,r⟩ (3 reads 1 write + scalars,
                        vs 2r/1w + 2×2r for the separate dots)
  * ``dot2``:           ⟨u,v⟩, ⟨v,v⟩                 (2 reads, vs 4)
  * ``dots_block``:     the (s_u × s_v) Gram block UVᵀ of two stacked vector
                        blocks in ONE pass over the data (s_u + s_v reads
                        total, vs 2·s_u·s_v reads for pairwise dot2 calls) —
                        the s-step solvers' all-dots-for-s-iterations reduce
                        (core/sstep.py).

1-D grid over VMEM-sized chunks; each grid step writes its per-block
partial sums as scalars into a (n_blocks,) SMEM output (a rank-1 VMEM block
of one element is not a legal Mosaic tile), reduced by the (tiny) jnp.sum in
ops.py. All accumulation in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 64 * 1024  # 64k f32 elements = 256 KiB per operand tile in VMEM
# whole-array scalar output resident in SMEM across the (sequential) grid
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _x_update_kernel(alpha_ref, gamma_ref, x_ref, p_ref, s_ref, o_ref):
    a = alpha_ref[0]
    g = gamma_ref[0]
    o_ref[...] = (
        x_ref[...].astype(jnp.float32)
        + a * p_ref[...].astype(jnp.float32)
        + g * s_ref[...].astype(jnp.float32)
    ).astype(o_ref.dtype)


def x_update(x, p, s, alpha, gamma, *, block=BLOCK, interpret=False):
    """x + alpha*p + gamma*s over flat f32 vectors (padded to block)."""
    n = x.shape[0]
    nb = pl.cdiv(n, block)
    scal = lambda v: jnp.asarray([v], jnp.float32) if jnp.ndim(v) == 0 else v.reshape(1)
    return pl.pallas_call(
        _x_update_kernel,
        name="cg_x_update",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(scal(alpha), scal(gamma), x, p, s)


def _residual_dots_kernel(gamma_ref, s_ref, As_ref, r0s_ref, r_ref, d1_ref, d2_ref):
    g = gamma_ref[0]
    r = s_ref[...].astype(jnp.float32) - g * As_ref[...].astype(jnp.float32)
    r_ref[...] = r
    i = pl.program_id(0)
    d1_ref[i] = jnp.sum(r * r0s_ref[...].astype(jnp.float32))
    d2_ref[i] = jnp.sum(r * r)


def residual_dots(s, As, r0s, gamma, *, block=BLOCK, interpret=False):
    """r = s - gamma*As; returns (r, per-block <r,r0s>, per-block <r,r>)."""
    n = s.shape[0]
    nb = pl.cdiv(n, block)
    scal = lambda v: jnp.asarray([v], jnp.float32) if jnp.ndim(v) == 0 else v.reshape(1)
    r, d1, d2 = pl.pallas_call(
        _residual_dots_kernel,
        name="cg_residual_dots",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)), _SMEM, _SMEM],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((nb,), jnp.float32),
            jax.ShapeDtypeStruct((nb,), jnp.float32),
        ],
        interpret=interpret,
    )(scal(gamma), s, As, r0s)
    return r, d1, d2


def _dots_block_kernel(u_ref, v_ref, o_ref):
    u = u_ref[...].astype(jnp.float32)      # (s_u, block)
    v = v_ref[...].astype(jnp.float32)      # (s_v, block)
    o_ref[0] = jax.lax.dot_general(
        u, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


# The Gram kernel streams s_u + s_v row vectors per grid step, so its column
# tile is narrower than the single-vector fusions' (s rows of 16k f32 =
# 64 KiB/row in VMEM; at s ≤ 16 this stays well inside the ~16 MB budget).
BLOCK_GRAM = 16 * 1024


def dots_block(U, V, *, block=BLOCK_GRAM, interpret=False):
    """Per-column-block partials of the Gram matrix U @ Vᵀ.

    ``U``: (s_u, n), ``V``: (s_v, n) stacked flat f32 vectors (n padded to a
    block multiple, rows padded to the sublane tile by ops.py). Returns
    (n_blocks, s_u, s_v) partials; the (tiny) reduction over blocks — the
    s-step solvers' ONE communication point per s Krylov iterations — happens
    in ops.py.
    """
    su, n = U.shape
    sv = V.shape[0]
    nb = pl.cdiv(n, block)
    return pl.pallas_call(
        _dots_block_kernel,
        name="cg_dots_block",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((su, block), lambda i: (0, i)),
            pl.BlockSpec((sv, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, su, sv), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, su, sv), jnp.float32),
        interpret=interpret,
    )(U, V)


def _dot2_kernel(u_ref, v_ref, d1_ref, d2_ref):
    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    i = pl.program_id(0)
    d1_ref[i] = jnp.sum(u * v)
    d2_ref[i] = jnp.sum(v * v)


def dot2(u, v, *, block=BLOCK, interpret=False):
    """Per-block partials of (<u,v>, <v,v>)."""
    n = u.shape[0]
    nb = pl.cdiv(n, block)
    return pl.pallas_call(
        _dot2_kernel,
        name="cg_dot2",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[_SMEM, _SMEM],
        out_shape=[
            jax.ShapeDtypeStruct((nb,), jnp.float32),
            jax.ShapeDtypeStruct((nb,), jnp.float32),
        ],
        interpret=interpret,
    )(u, v)
