"""Pallas TPU kernels for the perf-critical hot spots, with pure-jnp oracles.

  flash_attention — blockwise online-softmax attention: forward (+logsumexp
                    residual), backward dQ / dK+dV passes, and a JVP pass
  flash_ad        — the AD closure over those kernels (custom_jvp +
                    linear_call; ``second_order_tangents`` for the
                    exact-Hessian forward-over-reverse traces)
  cg_fused        — fused Bi-CG-STAB vector recurrences (the paper's
                    HBM-bound Krylov inner loop)
  ssd_scan        — Mamba2/SSD intra-chunk kernel (zamba2/xLSTM hot-spot)
  mlp_hvp         — the exact Hessian-vector product of a tanh MLP with a
                    softmax cross-entropy head, one pass over row tiles
                    (the Krylov solve's curvature product, models/mlp.py)

Validated in interpret mode on CPU against the pure-jnp oracles; compiled
path targets TPU.
"""
from . import flash_ad, mlp_hvp, ops, ref, ssd_scan
from .ops import (
    bicgstab_residual_dots,
    bicgstab_x_update,
    dot2,
    flash_attention,
    flash_attention_bwd,
    flash_attention_fwd,
    flash_attention_jvp,
    second_order_tangents,
)
from .ssd_scan import ssd_chunked_pallas, ssd_intra

__all__ = ["flash_ad", "mlp_hvp", "ops", "ref", "ssd_scan",
           "bicgstab_residual_dots", "bicgstab_x_update", "dot2",
           "flash_attention", "flash_attention_bwd", "flash_attention_fwd",
           "flash_attention_jvp", "second_order_tangents",
           "ssd_chunked_pallas", "ssd_intra"]
