"""Required matmul FLOPs of one outer HF step, counted from shapes.

A configuration's module lists its forward pass's matrix products as
``(flops, n_dep)``: ``flops = 2·m·k·n`` and ``n_dep`` the number of operands
that depend on the parameters (1 where the other operand is data, 2 where
both are weights or activations). The passes are then counted per product
``Y = A·B`` of ``f`` FLOPs:

* forward: ``f``.
* gradient (forward + backward): ``f + n_dep·f`` — the backward forms one
  product per operand that needs a gradient (``dA = dY·Bᵀ``, ``dB = Aᵀ·dY``).
  An operand that is data needs none.
* exact Hessian-vector product (Pearlmutter's R-operator, forward over
  reverse, on a linearization whose primal is cached): the tangent of the
  forward, ``dY = dA·B + A·dB``, costs one product per dependent operand;
  the tangent of each backward product ``dA = dY·Bᵀ`` is
  ``d(dY)·Bᵀ + dY·d(B)ᵀ``, one product plus one more where ``B`` also
  depends on the parameters. So ``n_dep = 1`` costs ``2f`` and
  ``n_dep = 2`` costs ``6f``: the "about six passes" of a curvature product.
  The primal forward and backward that the linearization caches are counted
  once per step, as a gradient on the curvature rows.

Nothing recomputed counts: a program that re-runs the primal in every
product does more work than this, not more required work.

One outer Bi-CG-STAB step (``core/solvers.py``) applies the operator once
for the initial residual and twice per iteration; the line search evaluates
the full-batch loss ``ls_evals`` times (``core/line_search.py``).
"""
from __future__ import annotations

HVP_PASSES = {1: 2.0, 2: 6.0}


def forward(mms) -> float:
    return sum(f for f, _ in mms)


def gradient(mms) -> float:
    return sum(f * (1 + n) for f, n in mms)


def hvp(mms) -> float:
    return sum(f * HVP_PASSES[n] for f, n in mms)


def bicgstab_products(cg_iters: float) -> float:
    """Operator applications of one Bi-CG-STAB solve of ``cg_iters``
    iterations: r0, then A·p̂ and A·ŝ per iteration."""
    return 1.0 + 2.0 * cg_iters


def hf_step(full, curv, cg_iters: float, ls_evals: float) -> float:
    """FLOPs of one outer step: the full-batch gradient, the curvature
    linearization's primal, the solve's products on the curvature rows, and
    the line search's full-batch forwards. ``full``/``curv`` are the
    matmul lists at the full and at the curvature batch."""
    return (gradient(full) + gradient(curv)
            + bicgstab_products(cg_iters) * hvp(curv)
            + ls_evals * forward(full))
