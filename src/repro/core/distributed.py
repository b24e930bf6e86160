"""Explicit data-parallel HF step via shard_map — the paper's Algorithm 2
with its MPI schedule written out.

Under pjit/GSPMD the collectives are implicit (sharding propagation inserts
them); this module is the *explicit* form: each worker holds a batch shard,
the loss is ``pmean``-ed over the data axes, and therefore

  * ``jax.grad``   of the pmean'd loss  = local grad + ONE all-reduce
                                          (Alg. 2 line 4, "reduce to root"),
  * each HVP       (jvp of that grad)   = local HVP + ONE all-reduce per
                                          Krylov iteration (line 5),
  * each line-search trial              = ONE scalar all-reduce (line 9).

Every reduction goes through ``core.collectives.preduce`` (a tagged
``lax.pmean``), so the schedule is *auditable*: the static jaxpr walk
(``jaxpr_collective_counts``) and the executed-collective counter
(``count_executed``) both validate ``metrics["krylov_syncs"]`` /
``metrics["blocking_syncs"]`` against the program that actually ran —
see tests/test_collective_audit.py and benchmarks/fig5_scaling.py
--executed.

**Sync schedule per outer HF step** (K Krylov iterations, E line-search
evaluations; "blocking" = a round-trip whose result gates the next launch):

  schedule                      all-reduces             blocking syncs
  ----------------------------  ----------------------  ----------------------
  standard (sstep_s=1)          1 + K + E               1 + K + E
  s-step (s>1)                  1 + K + ceil(K/s) + E   1 + ceil(K/s) + E
  s-step + overlap              1 + K + ceil(K/2s) + E  ceil(K/2s) + ceil(E/2)

  * s-step keeps one matvec all-reduce per iteration (the K term) but those
    pipeline back-to-back inside a cycle's chain phase with no scalar gate;
    the Gram reduce (ceil(K/s)) is the only blocking sync of the solve.
  * overlap (HFConfig.overlap) double-buffers cycles — TWO cycles of
    coordinate recurrences per Gram reduce (ceil(K/2s)) — hides the
    gradient all-reduce behind the curvature operator's primal build
    (the leading 1 stops blocking), and pairs line-search trials so two
    loss reduces share one round-trip (ceil(E/2)). Same arithmetic, same
    accepted step; only the schedule changes.

Everything else (Krylov recurrences, damping, direction selection) operates
on replicated state, exactly like the paper's root-node logic — except no
root: every chip is the root. The resulting step is numerically identical to
the pjit path (tested) — use whichever fits the deployment; GSPMD can
overlap/schedule, shard_map makes the schedule auditable.

This very schedule runs multi-process — N real processes, gloo CPU
collectives or a TPU pod — through ``launch/multiproc.py`` +
``launch/train.py --num-processes N`` (mesh from
``launch.mesh.make_data_mesh``); tests/test_multiproc.py holds the
2-process parity and executed-sync-count checks.

Because the Krylov state is per-chip *replicated* here (pure data
parallelism), this is exactly the deployment where
``HFConfig(krylov_backend="flat")`` pays: the solve ravels the replicated
iterates into one flat buffer per chip and runs the recurrences through the
fused Pallas kernels with zero extra communication (the collectives all live
inside the loss/HVP operator applications). Under pjit with *sharded*
params, keep the default "tree" backend — the flat ravel would break
per-tensor shardings.

Every ``HFConfig.curvature_mode`` composes with this schedule unchanged:
the curvature engine receives ``grad_reduce=pmean`` and applies it once per
accumulated product, so in "chunked" mode each worker scans its *local*
batch shard chunk-by-chunk, accumulates locally, and still issues exactly
one all-reduce per Krylov iteration (see core/curvature.py, sharding story).

**s-step × backend interaction** (``HFConfig.sstep_s > 1`` — core/sstep.py):
the s-step solvers change WHAT synchronizes, and each backend realizes the
saving differently:

  * Under this shard_map schedule (replicated Krylov state), each basis
    matvec is still one ``pmean`` — but the basis phase is a pure matvec
    chain with NO scalar gates between products, so those collectives
    pipeline back-to-back instead of alternating with blocking
    dot-round-trips; the one *blocking* sync per s iterations is the Gram.
    Width-2 block products additionally halve the collective count of the
    chain phase: the vmapped ``grad_reduce`` pmean carries the stacked
    pair in ONE collective (core/blocks.py).
  * Under pjit/GSPMD with **sharded** params ("tree" backend — the right
    choice there), every standard-iteration dot is a per-shard reduction +
    a scalar all-reduce whose result gates the next step.
    ``TreeVectorBackend.gram`` keeps the sharding-preserving form (per-leaf
    ``dot_general`` contractions, no reshape — §Perf pair A) and turns s
    iterations' worth of those blocking scalar syncs into one small
    (basis × basis) matrix all-reduce per cycle.
  * With per-chip replicated state ("flat" backend, this module's regime),
    the Gram runs through the fused Pallas ``dots_block`` kernel: one pass
    over the stacked basis per cycle with zero extra communication.

The Gram-guard fallback re-enters the standard solver with the SAME
backend and ``grad_reduce``, so a breakdown never changes the collective
schedule's correctness — only its count (reported per step as
``metrics["krylov_syncs"]`` / ``metrics["sstep_fallback"]``).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
from jax.sharding import PartitionSpec as P

from ..obs import telemetry as _telemetry
from .collectives import preduce
from .hf import HFConfig, hf_step


def data_parallel_hf_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    mesh,
    config: HFConfig,
    *,
    data_axes: Sequence[str] = ("data",),
    hvp_frac: float = 1.0,
    model_out_fn=None,
    out_loss_fn=None,
):
    """Returns step(params, state, batch) -> (params, state, metrics).

    ``batch`` leaves are sharded on their leading dim over ``data_axes``;
    params/state are replicated (pure data parallelism, the paper's setting:
    "we assume the size of the model is not huge").
    """
    axes = tuple(data_axes)

    def dloss(p, b):
        return preduce(loss_fn(p, b), axes, tag="loss")

    def dout_loss(z, b):
        return preduce(out_loss_fn(z, b), axes, tag="out_loss")

    def hvp_slice(b):
        if hvp_frac >= 1.0:
            return b
        return jax.tree_util.tree_map(
            lambda x: x[: max(int(x.shape[0] * hvp_frac), 1)], b
        )

    # NOTE: the gradient/HVP all-reduces are EXPLICIT (grad_reduce=pmean
    # below). Reverse-mode through the pmean'd loss leaves each worker with
    # its full *local* gradient contribution (no cross-worker reduction
    # appears in the transpose); pmean-ing the AD outputs — (1/N)Σ_w g_w,
    # matching the pmean'd loss — is Alg. 2's "reduce to root", one reduce
    # for g and one per Krylov iteration. The varying-manual-axes check is
    # OFF on purpose: under it, reverse mode through the replicated params
    # inserts its own per-leaf psum at every implicit pvary, on top of the
    # explicit, audited grad_reduce — 21 all-reduces instead of 6 in the
    # compiled bicgstab step of a 4-device mesh — and every pallas_call (flat
    # backend, flash attention) would have to declare the vma of its
    # outputs. That the outputs really are replicated is checked by
    # tests/test_distributed.py (N-device step == 1-device step) instead.
    def grad_reduce(t):
        return preduce(t, axes, tag="grad_hvp")

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(axes)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def step(params, state, batch):
        with _telemetry.phase("curvature_primal"):
            hvp_batch = hvp_slice(batch)
        return hf_step(
            dloss, params, state, batch, hvp_batch, config,
            model_out_fn=model_out_fn,
            out_loss_fn=None if out_loss_fn is None else dout_loss,
            grad_reduce=grad_reduce,
        )

    return step
