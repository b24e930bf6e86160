"""Unified optimizer interface: first-order baselines and the paper's HF
variants behind one (init, step) surface, selected by HFOptConfig.name.

HF steps take the full batch for gradient/line-search and slice a curvature
mini-batch of ``hvp_batch_frac`` (paper Alg. 2: full gradient, mini-batch
Hessian; Fig. 4 sweeps this size).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import HFOptConfig
from ..core import HFConfig, hf_init, hf_step
from ..obs import telemetry
from .first_order import adam, momentum_sgd, sgd

FIRST_ORDER = ("sgd", "momentum", "adam")


class Optimizer(NamedTuple):
    name: str
    init: Callable[[Any], Any]
    step: Callable[..., tuple]  # (params, state, batch) -> (params, state, metrics)


def _slice_batch(batch, frac: float):
    """Leading-dim slice for the curvature mini-batch (static fraction)."""
    if frac >= 1.0:
        return batch

    def cut(x):
        n = max(int(x.shape[0] * frac), 1)
        return x[:n]

    return jax.tree_util.tree_map(cut, batch)


def make_optimizer(
    opt: HFOptConfig,
    loss_fn,
    model_out_fn=None,
    out_loss_fn=None,
    mesh=None,
    data_axes=("data",),
) -> Optimizer:
    """``mesh`` selects the explicit data-parallel step: the HF step is
    wrapped in shard_map over ``data_axes`` (core.distributed — batch leaves
    sharded on their leading dim, params/state replicated, the paper's MPI
    schedule written out). Works for single- AND multi-process meshes
    (launch/multiproc.py); first-order optimizers don't take a mesh here.
    """
    if opt.name in FIRST_ORDER:
        if mesh is not None:
            raise ValueError(
                "mesh= is only supported for the HF optimizers "
                f"(got first-order {opt.name!r})"
            )
        fo = {
            "sgd": lambda: sgd(opt.lr),
            "momentum": lambda: momentum_sgd(opt.lr, opt.momentum),
            "adam": lambda: adam(opt.lr),
        }[opt.name]()

        def step(params, state, batch):
            return fo.step(loss_fn, params, state, batch)

        return Optimizer(opt.name, fo.init, step)

    hf_cfg = HFConfig(
        solver=opt.name,
        max_cg_iters=opt.max_cg_iters,
        cg_tol=opt.cg_tol,
        init_damping=opt.init_damping,
        cg_decay=opt.cg_decay,
        precondition=opt.precondition,
        krylov_backend=opt.krylov_backend,
        curvature_mode=opt.curvature_mode,
        curvature_chunk_size=opt.curvature_chunk_size,
        sstep_s=opt.sstep_s,
        sstep_solver=opt.sstep_solver,
        sstep_basis=opt.sstep_basis,
        overlap=opt.overlap,
        nc_mode=opt.nc_mode,
        reject_nonfinite=opt.reject_nonfinite,
        strict_descent=opt.strict_descent,
        descent_guard=opt.descent_guard,
        reject_boost=opt.reject_boost,
    )

    def init(params):
        return hf_init(params, hf_cfg)

    if mesh is not None:
        from ..core.distributed import data_parallel_hf_step

        step = data_parallel_hf_step(
            loss_fn, mesh, hf_cfg, data_axes=tuple(data_axes),
            hvp_frac=opt.hvp_batch_frac,
            model_out_fn=model_out_fn, out_loss_fn=out_loss_fn,
        )
        return Optimizer(opt.name, init, step)

    def step(params, state, batch):
        with telemetry.phase("curvature_primal"):
            hvp_batch = _slice_batch(batch, opt.hvp_batch_frac)
        return hf_step(
            loss_fn, params, state, batch, hvp_batch, hf_cfg,
            model_out_fn=model_out_fn, out_loss_fn=out_loss_fn,
        )

    return Optimizer(opt.name, init, step)
