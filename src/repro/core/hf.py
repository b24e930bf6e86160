"""Distributed Hessian-free optimizer — paper Algorithm 2 as one jitted step.

Variants (``HFConfig.solver``):
  * ``"gn_cg"``      — Martens' HF: Gauss-Newton operator + CG (PSD; baseline).
  * ``"hessian_cg"`` — exact stochastic Hessian + truncated CG (paper shows
                       this is unstable — reproduced as a baseline).
  * ``"hybrid_cg"``  — exact Hessian CG; after an iteration that encountered
                       negative curvature, the *next* iteration uses the
                       Gauss-Newton operator, then switches back (paper §5).
  * ``"bicgstab"``   — the paper's contribution: Bi-CG-STAB on the indefinite
                       exact Hessian; negative-curvature directions are
                       captured and used as saddle-escape steps.

The step is pure and jittable; under pjit with the batch sharded over
("pod","data") every gradient / HVP / line-search loss evaluation contains
exactly one logical all-reduce — the paper's MPI schedule (one reduce for g,
one per Krylov iteration, one per line-search trial).

The inner Krylov solve runs on a swappable vector backend
(``HFConfig.krylov_backend``): "tree" (pytree iterates, sharding-preserving)
or "flat" (ravelled f32 iterates through the fused Pallas kernels — see
core.krylov). Both yield the same KrylovResult; solver math is identical.

The curvature operator itself comes from the curvature engine
(``HFConfig.curvature_mode`` — core.curvature): the default "linearize" mode
runs the primal forward/backward once per outer step and feeds the Krylov
loop the cached linear map; "chunked" adds flat-memory accumulation over
``curvature_chunk_size``-example microbatches for the paper's Fig. 4
large-curvature-batch regime. When the curvature mini-batch is the full
batch, a single ``jax.linearize(jax.value_and_grad(loss))`` pass yields f0,
g AND the cached Hessian map together (shared primal — one fewer
forward+backward per outer step).

``HFConfig.sstep_s > 1`` swaps the Krylov solve for its s-step
(communication-avoiding) form (core.sstep): per cycle of s iterations the
solver grows a polynomial basis (``HFConfig.sstep_basis``: monomial power
chains, or Ritz-parameterized shifted-Newton/Chebyshev chains that double
the usable depth) with width-2 *block* curvature products (core.blocks —
same cached linearization, residuals read once per pair) and collapses all
of the cycle's dot products into ONE Gram reduction — ``1 + ceil(K/s) + E``
blocking reduces per outer step instead of ``1 + K + E``
(benchmarks/comm_model.py), with a Gram-factorization guard whose fallback
chain (adaptive basis → monomial → standard solver) never lets correctness
depend on a basis surviving.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import damping as damping_mod
from .blocks import block_op_from_single
from .curvature import (
    MODES as CURVATURE_MODES,
    make_damped,
    make_gnvp_op,
    make_hvp_op,
    shared_primal_hvp,
)
from ..kernels.flash_ad import second_order_tangents
from ..obs import telemetry as _telemetry
from .krylov import BACKENDS, get_backend
from .line_search import armijo
from .solvers import bicgstab, cg, hutchinson_diag, pcg, sign_correct
from .sstep import BASES as SSTEP_BASES, sstep_bicgstab, sstep_cg
from .tree_math import (
    tree_axpy,
    tree_axpy_cast,
    tree_dot,
    tree_norm,
    tree_pseudo_noise,
    tree_scale,
    tree_where,
    tree_zeros_like,
)

SOLVERS = ("gn_cg", "hessian_cg", "hybrid_cg", "bicgstab")
SSTEP_SOLVERS = ("auto", "cg", "bicgstab")
NC_MODES = ("truncate", "escape")

# The complete per-step metrics contract of ``hf_step``: every key it
# returns, each a finite scalar (asserted by tests/test_telemetry.py's
# metrics-contract test; hf_step itself checks the key set at trace time).
# The train loop adds host-side fields on top — "step", "wall_s" and (step
# 0 only) "compile_s" — which are NOT part of this in-jit contract.
METRICS_SCHEMA = (
    "loss", "loss_new", "grad_norm", "lambda", "rho", "alpha", "ls_evals",
    "cg_iters", "cg_residual", "krylov_syncs", "blocking_syncs",
    "sstep_fallback", "sstep_basis_fallback", "sstep_basis_degraded",
    "nc_found", "nc_used", "nc_curv", "nc_lambda", "step_norm", "used_gn",
    "step_rejected",
)


@dataclasses.dataclass(frozen=True)
class HFConfig:
    solver: str = "bicgstab"
    max_cg_iters: int = 16
    cg_tol: float = 5e-3
    init_damping: float = 1.0
    damping_inc: float = 1.5
    damping_dec: float = 1.5
    cg_decay: float = 0.95        # η: Krylov warm-start θ_0 = η δ_{k-1}
    ls_c: float = 1e-2            # Armijo sufficient-decrease constant
    ls_beta: float = 0.5
    max_backtracks: int = 12
    # Relative jitter on the Krylov warm start. Enriches the Krylov space with
    # directions orthogonal to g so negative curvature invisible to the exact
    # deterministic recurrence (g ⟂ eigenvector, e.g. the Fig. 2 saddle) is
    # still discoverable — the same role mini-batch Hessian noise plays in the
    # paper's stochastic setting, made deterministic and controllable.
    krylov_jitter: float = 1e-3
    # Minimum norm for a negative-curvature step: along NC directions the
    # quadratic model is unbounded below so it prescribes no scale; we take at
    # least this much and let the Armijo search (Alg. 2 line 9) globalize it.
    nc_min_step: float = 0.1
    # What to do when the NC probe fires (the paper's differentiator over
    # Martens-style HF is exploiting indefinite curvature):
    #   * "truncate" — the historical passive policy: the NC direction
    #     competes with the solver iterate under the damped quadratic model
    #     at the solution's norm scale (floored at nc_min_step).
    #   * "escape"   — saddle-free offense (Arjovsky, arXiv:1506.00059):
    #     an explicit escape step along the NC direction scaled by
    #     |λ_min(G)|, the solver's eigenvalue estimate threaded through
    #     KrylovResult.nc_lambda (Rayleigh quotient from the standard
    #     recurrences, refined by per-cycle Ritz values from the s-step
    #     Grams — free, no extra reductions). The candidate is judged by
    #     the RAW (undamped) model, which is unbounded below along true NC,
    #     so a fired probe nearly always takes the escape step; the Armijo
    #     search globalizes it and the divergence sentinel
    #     (reject_nonfinite) guards the new step family — a non-finite λ
    #     estimate yields a non-finite step that is REJECTED, never
    #     silently masked.
    nc_mode: str = "truncate"
    # Jacobi preconditioning: M = (|diag(Ĝ)| + λ)^α estimated by one
    # Hutchinson probe per step. CG-family solvers use PCG; Bi-CG-STAB uses
    # its right-preconditioned form. The paper omits it ("not much helpful,
    # more computation and storage") — off by default, available for the
    # ill-conditioned regimes where it does pay.
    precondition: bool = False
    precond_alpha: float = 0.75
    # Krylov vector backend (core.krylov): "tree" keeps iterates as pytrees
    # (sharding-preserving; right when params are sharded under pjit);
    # "flat" ravels them once per solve and runs the recurrences through the
    # fused Pallas kernels (right for per-chip-replicated Krylov state, the
    # paper's pure data-parallel setting; interpret-mode off-TPU).
    krylov_backend: str = "tree"
    # Curvature engine (core.curvature): "linearize" runs the primal
    # forward/backward once per outer step and each Krylov iteration applies
    # only the cached linear map; "chunked" additionally accumulates G·v over
    # lax.scan microbatches of `curvature_chunk_size` examples (flat memory
    # in the curvature batch — paper Fig. 4's 10× larger hvp batches);
    # "naive" is the historical rebuild-per-call closure (baselines,
    # EXPERIMENTS.md §Perf pair D).
    curvature_mode: str = "linearize"
    curvature_chunk_size: int = 0     # examples per microbatch (chunked mode;
                                      # <=0 or >=batch ⇒ one whole-batch chunk)
    curvature_remat: bool = True      # jax.checkpoint the chunk body (chunked
                                      # HVP; chunked GN is flat-memory as-is)
    # s-step (communication-avoiding) Krylov solve (core.sstep): sstep_s > 1
    # replaces the standard recurrence with the s-step form — per cycle of s
    # iterations the solver grows a polynomial basis (matvecs only, paired
    # into width-2 block curvature products through the SAME cached
    # linearization) and issues ONE Gram reduction in place of s
    # per-iteration dot syncs (1 + ceil(K/s) + E reduces per outer step vs
    # 1 + K + E — see benchmarks/comm_model.py). A Gram-factorization guard
    # falls back to the standard solver when the basis conditioning
    # degrades, so correctness never depends on the basis surviving.
    # sstep_solver picks the s-step recurrence: "auto" derives it from
    # `solver` (bicgstab ⇒ s-step Bi-CG-STAB, the CG family ⇒ s-step CG);
    # "cg"/"bicgstab" force one. Incompatible with `precondition` (the
    # s-step recurrences are unpreconditioned; rejected at config time).
    sstep_s: int = 1
    sstep_solver: str = "auto"
    # Basis polynomial for the s-step chains (core.sstep.BASES):
    # "monomial" is the classic power chain — simple, but its f32 depth
    # budget caps usable s at ~4 (CG) / 2 (Bi-CG-STAB); "newton"
    # (Leja-ordered shifted-Newton) and "chebyshev" (Ritz-interval
    # Chebyshev) are conditioned bases parameterized by Ritz estimates the
    # cycle Gram already contains for free (bootstrapped from one f32-safe
    # monomial cycle, refreshed every cycle inside the jitted loop) — they
    # roughly double usable s (CG s=8, Bi-CG-STAB s=4: EXPERIMENTS.md
    # §Perf pair G), with a fallback chain Newton/Chebyshev → monomial →
    # standard solver on guard failure.
    sstep_basis: str = "monomial"
    # Overlapped collective schedule (the executed Fig. 5 harness's
    # double-buffered mode — benchmarks/fig5_scaling.py --executed):
    #   * s-step cycles are double-buffered (core.sstep overlap=True): two
    #     cycles share one Gram reduction, its all-reduce hidden behind the
    #     second cycle's chain growth; the speculative deep half runs under
    #     the depth-resolved prefix guard, so it never converges worse than
    #     the non-overlapped schedule at the same s.
    #   * the gradient all-reduce is issued concurrently with the curvature
    #     engine's primal build (no data dependence) instead of gating it —
    #     its latency hides behind a model-sized forward.
    #   * the Armijo search evaluates candidate PAIRS per trip
    #     (core.line_search paired=True): same accepted α, ⌈E/2⌉ blocking
    #     scalar round-trips instead of E.
    # metrics["blocking_syncs"] reports the executed blocking count either
    # way; benchmarks/comm_model.py carries the overlap=True formula.
    overlap: bool = False
    # Divergence sentinel (robustness — see tests/test_hf_robustness.py and
    # benchmarks/chaos_check.py). The repo deliberately runs INDEFINITE
    # stochastic Hessians through Bi-CG-STAB, so a poisoned curvature batch
    # (NaN/Inf activations, corrupted shard) can hand the line search a
    # non-finite direction; without a guard the `0 * NaN = NaN` update
    # poisons the parameters forever. With ``reject_nonfinite`` (default
    # on) an outer step whose accepted loss or step norm is non-finite is
    # REJECTED: params and warm start are kept, λ is boosted through the
    # existing Levenberg-Marquardt machinery (``reject_boost``; 0 ⇒
    # damping_inc²), and metrics["step_rejected"] / a telemetry fault
    # event record it. ``strict_descent`` additionally rejects any step
    # whose new loss exceeds f0 + descent_guard·max(1, |f0|) — off by
    # default (the Armijo search already enforces sufficient decrease;
    # strict mode is for chaos/fault-injection runs where the loss itself
    # may be computed from poisoned data).
    reject_nonfinite: bool = True
    strict_descent: bool = False
    descent_guard: float = 0.0
    reject_boost: float = 0.0

    def __post_init__(self):
        if self.solver not in SOLVERS:
            raise ValueError(f"solver must be one of {SOLVERS}, got {self.solver!r}")
        if self.krylov_backend not in BACKENDS:
            raise ValueError(
                f"krylov_backend must be one of {BACKENDS}, got {self.krylov_backend!r}"
            )
        if self.curvature_mode not in CURVATURE_MODES:
            raise ValueError(
                f"curvature_mode must be one of {CURVATURE_MODES}, "
                f"got {self.curvature_mode!r}"
            )
        if self.sstep_solver not in SSTEP_SOLVERS:
            raise ValueError(
                f"sstep_solver must be one of {SSTEP_SOLVERS}, "
                f"got {self.sstep_solver!r}"
            )
        if self.sstep_basis not in SSTEP_BASES:
            raise ValueError(
                f"sstep_basis must be one of {SSTEP_BASES}, "
                f"got {self.sstep_basis!r}"
            )
        if self.nc_mode not in NC_MODES:
            raise ValueError(
                f"nc_mode must be one of {NC_MODES}, got {self.nc_mode!r}"
            )
        if self.sstep_s > 1 and self.precondition:
            raise ValueError(
                "sstep_s > 1 is incompatible with precondition=True: the "
                "s-step recurrences are unpreconditioned (use the standard "
                "solvers for Jacobi preconditioning)"
            )


class HFState(NamedTuple):
    lam: jax.Array          # λ damping
    prev_delta: Any         # δ_{k-1} for Krylov warm start
    use_gn: jax.Array       # hybrid flag: this iteration uses GN operator
    step: jax.Array


def hf_init(params, config: HFConfig) -> HFState:
    return HFState(
        lam=jnp.asarray(config.init_damping, jnp.float32),
        # Krylov warm-start lives in f32 even for bf16 params (recurrence
        # numerics); the HVP operator casts at its boundary.
        prev_delta=jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        ),
        use_gn=jnp.zeros((), bool),
        step=jnp.zeros((), jnp.int32),
    )


def hf_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    params,
    state: HFState,
    batch,
    hvp_batch,
    config: HFConfig,
    model_out_fn: Optional[Callable[[Any, Any], jax.Array]] = None,
    out_loss_fn: Optional[Callable[[jax.Array, Any], jax.Array]] = None,
    grad_reduce: Optional[Callable[[Any], Any]] = None,
):
    """One outer HF iteration. Returns (params, state, metrics).

    ``batch``     — the full (global) batch: gradient + line search.
    ``hvp_batch`` — the mini-batch for stochastic curvature (may be a slice of
                    ``batch``; larger ⇒ better Hessian approximation, the
                    paper's Fig. 4 batch-size scaling).
    ``model_out_fn``/``out_loss_fn`` — network/loss split, required for the
    Gauss-Newton operator (``gn_cg`` and ``hybrid_cg``).
    ``grad_reduce`` — completion collective for AD results under explicit
    data parallelism (shard_map): applied to the gradient and to every
    curvature-operator output. Reverse-mode through a pmean'd loss yields
    each worker's full *local* contribution (the reduction the paper's
    "reduce to root" performs is not inserted by the transpose); the
    distributed wrapper passes ``lax.pmean`` here — Alg. 2's one reduce for
    g and one per Krylov iteration, made explicit. Under pjit/GSPMD leave it
    None (the partitioner inserts the collectives from sharding
    propagation).
    """
    needs_gn = config.solver in ("gn_cg", "hybrid_cg")
    if needs_gn and (model_out_fn is None or out_loss_fn is None):
        raise ValueError(f"solver {config.solver} requires model_out_fn/out_loss_fn")

    # ---- Alg.2 lines 3-5: gradient + stochastic curvature operator ---------
    # Curvature operators are built once per outer step by the curvature
    # engine: in "linearize"/"chunked" modes the primal forward+backward runs
    # HERE (hoisted out of the Krylov loop — and, for the hybrid solver, out
    # of the lax.cond branches, which XLA never hoists itself) and every
    # operator application below executes only the cached linear map.
    # grad_reduce is applied inside the engine, once per accumulated product.
    curv_kw = dict(
        mode=config.curvature_mode, chunk_size=config.curvature_chunk_size,
        remat=config.curvature_remat, grad_reduce=grad_reduce,
    )
    # Shared primal: when the curvature mini-batch IS the gradient batch and
    # the solver wants the exact Hessian, one jax.linearize(value_and_grad)
    # yields f0, g AND the cached Hessian map from a single forward+backward
    # (core.curvature.shared_primal_hvp) — one fewer primal pass per outer
    # step than value_and_grad + a separate engine build.
    shared = (
        config.curvature_mode == "linearize"
        and hvp_batch is batch
        and config.solver != "gn_cg"
    )
    # Telemetry (repro.obs): every region runs inside its named phase scope
    # (always on, metadata only: the device trace reads it); phase
    # end-markers + the grad-reduce collective label are trace-time no-ops
    # unless a sink is installed — the disabled jaxpr carries no callback
    # (tests/test_telemetry.py). step_scope hands state.step to markers
    # emitted from the curvature engine / s-step solvers.
    phase = _telemetry.phase
    curv_params = params      # the curvature primal's parameters (gated below)
    _telemetry.marker("step_begin", batch, step=state.step)
    with _telemetry.step_scope(state.step):
        if shared:
            with phase("grad_build"), _telemetry.collective_label("grad_reduce"):
                f0, g, exact = shared_primal_hvp(
                    loss_fn, params, batch, grad_reduce=grad_reduce
                )
        else:
            # ---- Alg.2 lines 3-4: full gradient (all-reduce under pjit) ----
            with phase("grad_build"):
                f0, g = jax.value_and_grad(loss_fn)(params, batch)
                _telemetry.marker("grad_build", f0, g, step=state.step)
            if grad_reduce is not None and not config.overlap:
                with phase("grad_reduce"):
                    with _telemetry.collective_label("grad_reduce"):
                        g = grad_reduce(g)
                    # With a sink, the primal is built from the parameters
                    # the marker hands on, so it starts after the reduce.
                    g, curv_params = _telemetry.gated_marker(
                        "grad_reduce", g, params, step=state.step)
            # Only build the operators the solver will apply: in the
            # linearized modes construction itself runs a primal pass
            # (eagerly, outside jit).
            if config.solver != "gn_cg":
                with phase("curvature_primal"):
                    exact = make_hvp_op(loss_fn, curv_params, hvp_batch,
                                        **curv_kw)
        if needs_gn:
            with phase("curvature_primal"):
                if config.sstep_s > 1:
                    # The s-step solve lifts its operator to stacked
                    # multi-tangent blocks via jax.vmap (core/blocks.py).
                    # The flash-attention first-order GN tangent
                    # (linear_call) has no batching rule, so build the GN
                    # operator under the AD-closed second-order rules —
                    # plain jnp, vmappable, same math; a no-op for models
                    # that don't use flash attention (kernels/flash_ad.py).
                    with second_order_tangents():
                        gn = make_gnvp_op(model_out_fn, out_loss_fn,
                                          curv_params, hvp_batch, **curv_kw)
                else:
                    gn = make_gnvp_op(model_out_fn, out_loss_fn, curv_params,
                                      hvp_batch, **curv_kw)
        if not shared and grad_reduce is not None and config.overlap:
            # Hidden grad-reduce (overlapped schedule): the model-sized
            # gradient all-reduce has no data dependence on the curvature
            # engine's primal build, so issuing it AFTER the operator
            # construction above lets the scheduler run the collective
            # concurrently with that forward — its first consumer is the
            # Krylov right-hand side, by which point the reduce has
            # completed. Counted as 0 blocking round-trips in
            # metrics["blocking_syncs"]. (The telemetry span of this very
            # collective — begin at input-ready, end at completion — is how
            # the overlap is MEASURED: obs/trace.py grad_reduce_overlap.)
            with phase("grad_reduce"), \
                    _telemetry.collective_label("grad_reduce"):
                g = grad_reduce(g)
    if config.solver == "gn_cg":
        G = gn
    elif config.solver in ("hessian_cg", "bicgstab"):
        G = exact
    else:  # hybrid: runtime switch between the two cached linear maps
        def G(v, _state_use_gn=state.use_gn):
            return jax.lax.cond(_state_use_gn, gn, exact, v)
    operator = G

    def G(v):
        # Every application of the operator — the damped product in each
        # Krylov iteration, the s-step block product (a vmap of it), the
        # Hutchinson probe — is its own scope nested in krylov_solve.
        with phase("curvature_product"):
            return operator(v)

    # ---- Alg.2 line 6: Krylov solve ----------------------------------------
    with phase("krylov_solve"):
        lam = state.lam
        A = make_damped(G, lam)
        b = jax.tree_util.tree_map(lambda x: -x.astype(jnp.float32), g)
        x0 = tree_scale(config.cg_decay, state.prev_delta)
        if config.krylov_jitter > 0.0:
            # Sharding-preserving pseudo-noise (NOT jax.random — see
            # tree_math.tree_pseudo_noise): seeded by the gradient values,
            # the element position and the step counter.
            jit_tree = tree_pseudo_noise(g, state.step)
            scale = (config.krylov_jitter * jnp.maximum(tree_norm(g), 1e-8)
                     / jnp.maximum(tree_norm(jit_tree), 1e-20))
            x0 = tree_axpy(scale, jit_tree, x0)

        # Vector backend: "tree" keeps the solve on sharding-preserving
        # pytrees; "flat" ravels once and runs the recurrences via the fused
        # Pallas kernels.
        krylov_be = get_backend(config.krylov_backend, template=b)
        m_inv = None
        if config.precondition:
            # The probe reuses the prebuilt operator G — under the
            # linearized modes each Hutchinson sample is one cached-linear-map
            # application, not a fresh re-linearization (EXPERIMENTS.md §Perf
            # pair D).
            diag = hutchinson_diag(G, b, state.step)
            m_inv = jax.tree_util.tree_map(
                lambda d: 1.0 / (jnp.abs(d) + lam) ** config.precond_alpha,
                diag)
        with _telemetry.step_scope(state.step):
            if config.sstep_s > 1:
                # s-step (communication-avoiding) solve: ONE Gram reduction per
                # cycle of sstep_s iterations, basis power chains paired into
                # width-2 block curvature products derived from the SAME cached
                # linearization as A (core.blocks.block_op_from_single — jax.vmap
                # over the operator, no second primal pass). Falls back to the
                # standard solver on basis-conditioning breakdown.
                kind = config.sstep_solver
                if kind == "auto":
                    kind = "bicgstab" if config.solver == "bicgstab" else "cg"
                sstep_fn = sstep_bicgstab if kind == "bicgstab" else sstep_cg
                res = sstep_fn(
                    A, b, x0, lam=lam, s=config.sstep_s,
                    max_iters=config.max_cg_iters, tol=config.cg_tol,
                    backend=krylov_be, A_block=block_op_from_single(A),
                    basis=config.sstep_basis, overlap=config.overlap,
                )
            elif config.solver == "bicgstab":
                res = bicgstab(A, b, x0, lam=lam, max_iters=config.max_cg_iters,
                               tol=config.cg_tol, M_inv=m_inv, backend=krylov_be)
            elif m_inv is not None:
                res = pcg(A, b, x0, lam=lam, M_inv=m_inv,
                          max_iters=config.max_cg_iters, tol=config.cg_tol,
                          backend=krylov_be)
            else:
                res = cg(A, b, x0, lam=lam, max_iters=config.max_cg_iters,
                         tol=config.cg_tol, backend=krylov_be)
        _telemetry.marker("krylov_solve", res.residual, res.x,
                          step=state.step)
        _telemetry.solve_event(
            state.step, iters=res.iters, residual=res.residual,
            syncs=res.syncs, residual_history=res.residual_history,
            nc_found=res.nc_found, breakdown=res.breakdown,
        )

    # ---- Alg.2 line 7: best descent direction among {solution, NC dir} -----
    with phase("direction"):
        # Quadratic-model values come FREE from solver byproducts — no extra
        # operator applications (each would cost a full HVP = 2 passes over the
        # network; see EXPERIMENTS.md §Perf pair C):
        #   A·x = b − r  (residual identity)  ⇒ m(s·x) = s·gᵀx + ½ xᵀ(b−r)
        #   nc_dir has unit norm and measured raw curvature c = dᵀGd
        #                                      ⇒ m(nc) = gᵀnc + ½ (c+λ)·‖nc‖²
        # free CG-backtracking: the direction candidate is the best-model iterate
        gx = tree_dot(g, res.x_best)
        sign = jnp.where(jnp.sign(gx) == 0, 1.0, -jnp.sign(gx))
        sol = tree_scale(sign, res.x_best)
        sol_norm = tree_norm(sol)
        xAx = tree_dot(res.x_best, jax.tree_util.tree_map(jnp.subtract, b, res.r_best))
        m_sol = sign * gx + 0.5 * xAx
        # λ_min(G) estimate for this solve: the solver's threaded nc_lambda
        # (Ritz-refined on the s-step paths) floored by the probe's Rayleigh
        # quotient, gated on the probe actually firing.
        nc_lam = jnp.where(
            res.nc_found, jnp.minimum(res.nc_lambda, res.nc_curv), 0.0)
        if config.nc_mode == "escape":
            # Saddle-free escape (Arjovsky, arXiv:1506.00059): step along the
            # (unit-norm) NC direction at the |λ_min| scale — the magnitude the
            # saddle-free Newton rescaling |H|⁻¹g prescribes along an
            # eigendirection — instead of borrowing the solution's norm. The
            # candidate is judged by the RAW (undamped) model, honest about
            # being unbounded below along true negative curvature, so a fired
            # probe nearly always escapes; Armijo globalizes the scale.
            nc_scale = jnp.abs(nc_lam)
            nc_raw = tree_scale(nc_scale, res.nc_dir)
            nc, _ = sign_correct(g, nc_raw)
            g_nc = tree_dot(g, nc)
            m_nc = jnp.where(
                res.nc_found,
                g_nc + 0.5 * res.nc_curv * nc_scale**2,
                jnp.inf,
            )
            # NaN-safe toward TAKING the step: a poisoned λ estimate (inf/NaN
            # scale) must reach the divergence sentinel below as a non-finite
            # step and be rejected there — `m_nc < m_sol` would silently mask
            # it (NaN compares False) and accept the solver iterate instead.
            take_nc = jnp.logical_and(
                res.nc_found, jnp.logical_not(m_sol <= m_nc))
        else:
            # Scale the (unit-norm) NC direction to the solution's magnitude so
            # the quadratic-model comparison and the line search see comparable
            # steps; the quadratic model itself is unbounded below along NC
            # directions so it prescribes no scale — floor at nc_min_step and
            # let Armijo globalize.
            nc_scale = jnp.maximum(sol_norm, config.nc_min_step)
            nc_raw = tree_scale(nc_scale, res.nc_dir)
            nc, _ = sign_correct(g, nc_raw)
            g_nc = tree_dot(g, nc)
            m_nc = jnp.where(
                res.nc_found,
                g_nc + 0.5 * (res.nc_curv + lam) * nc_scale**2,
                jnp.inf,
            )
            take_nc = m_nc < m_sol
        delta = tree_where(take_nc, nc, sol)
        m_lin = jnp.where(take_nc, g_nc, sign * gx)       # gᵀδ
        m_quad = jnp.where(take_nc, m_nc - g_nc, 0.5 * xAx)  # ½ δᵀAδ

        # Degenerate solve (zero direction) → steepest descent fallback (paper:
        # "if negative curvature at the very first CG iteration, use −g").
        d_norm = tree_norm(delta)
        degenerate = d_norm < 1e-12
        delta = tree_where(degenerate, b, delta)
        gg = tree_dot(g, g)
        m_lin = jnp.where(degenerate, -gg, m_lin)
        m_quad = jnp.where(degenerate, 0.0, m_quad)

    # ---- Alg.2 line 9: Armijo line search -----------------------------------
    with phase("line_search"):
        g_dot_delta = tree_dot(g, delta)
        ls = armijo(
            lambda p: loss_fn(p, batch), params, f0, delta, g_dot_delta,
            c=config.ls_c, beta=config.ls_beta, max_backtracks=config.max_backtracks,
            paired=config.overlap,
        )
        _telemetry.marker("line_search", ls.alpha, ls.f_new, step=state.step)

    # ---- Alg.2 lines 8,10: LM damping + parameter update --------------------
    with phase("update_damping"):
        # predicted reduction of the STEP TAKEN: m(αδ) = α·gᵀδ + α²·½δᵀAδ
        pred_red = ls.alpha * m_lin + ls.alpha**2 * m_quad
        pred_red = jnp.minimum(pred_red, -1e-20)
        lam_new, rho = damping_mod.lm_update(
            lam, f0, ls.f_new, pred_red,
            inc=config.damping_inc, dec=config.damping_dec,
        )
        new_params = tree_axpy_cast(ls.alpha, delta, params)
        delta_taken = tree_scale(ls.alpha, delta)

        # ---- divergence sentinel: reject poisoned / ascent steps ---------------
        # A non-finite accepted loss or step (poisoned curvature batch, solver
        # blow-up) must not reach the parameters: even the alpha=0 "zero step"
        # is `0 * NaN = NaN` leaf-wise when delta itself is non-finite. Reject:
        # keep params, drop the warm start (it would re-inject the poisoned
        # direction next step), boost λ through the LM machinery, and report it
        # (metrics["step_rejected"] + a `repro.obs` fault event). strict_descent
        # additionally rejects real loss increases beyond the guard.
        rejected = jnp.zeros((), bool)
        if config.reject_nonfinite or config.strict_descent:
            accept = jnp.ones((), bool)
            if config.reject_nonfinite:
                finite_ok = jnp.logical_and(
                    jnp.isfinite(ls.f_new), jnp.isfinite(tree_norm(delta_taken)))
                accept = jnp.logical_and(accept, finite_ok)
            if config.strict_descent:
                guard = config.descent_guard * jnp.maximum(1.0, jnp.abs(f0))
                accept = jnp.logical_and(accept, ls.f_new <= f0 + guard)
            rejected = jnp.logical_not(accept)
            boost = (config.reject_boost if config.reject_boost > 0
                     else config.damping_inc ** 2)
            lam_new = jnp.where(accept, lam_new,
                                jnp.clip(lam * boost, 1e-8, 1e8))
            rho = jnp.where(accept, rho, 0.0)
            new_params = tree_where(accept, new_params, params)
            delta_taken = tree_where(
                accept, delta_taken, tree_zeros_like(state.prev_delta))
        _telemetry.reject_event(state.step, rejected, lam_new, ls.f_new)

        if config.solver == "hybrid_cg":
            # NC encountered this (exact-Hessian) iteration → GN next iteration;
            # after a GN iteration always return to the exact Hessian.
            use_gn_next = jnp.logical_and(jnp.logical_not(state.use_gn), res.nc_found)
        else:
            use_gn_next = jnp.zeros((), bool)

        new_state = HFState(
            lam=lam_new, prev_delta=delta_taken, use_gn=use_gn_next, step=state.step + 1
        )
        _telemetry.marker("update_damping", lam_new, rho, new_params, step=state.step)
        metrics = {
            "loss": f0,
            "loss_new": ls.f_new,
            "grad_norm": tree_norm(g),
            "lambda": lam_new,
            "rho": rho,
            "alpha": ls.alpha,
            "ls_evals": ls.n_evals,
            "cg_iters": res.iters,
            "cg_residual": res.residual,
            # Blocking scalar-producing reductions the Krylov solve ran: one
            # per iteration for the standard recurrences, one Gram reduction per
            # s-iteration cycle for the s-step solvers (+ fallback iterations
            # when the basis guard fired — sstep_fallback). The quantity the
            # comm model's `1 + ceil(K/s) + E` counts (benchmarks/comm_model.py,
            # measured by benchmarks/sstep_bench.py).
            "krylov_syncs": res.syncs,
            # Executed BLOCKING synchronizations this outer step — round-trips
            # where the schedule stalls on a collective's result before the next
            # one can start: the gradient reduce (hidden behind the curvature
            # primal build under the overlapped schedule ⇒ 0), one per Krylov
            # sync (iterations / Gram cycles — double-buffered cycles already
            # halve res.syncs), and one per line-search trip (candidate PAIRS
            # under overlap ⇒ ⌈E/2⌉). The executed counterpart of
            # comm_model.hf_sstep_syncs_per_iteration(..., overlap=).
            "blocking_syncs": (
                res.syncs + (ls.n_evals + 1) // 2 if config.overlap
                else 1 + res.syncs + ls.n_evals
            ),
            "sstep_fallback": jnp.logical_and(config.sstep_s > 1, res.breakdown),
            # The subset of sstep_fallback caused by the GRAM GUARD (the basis
            # degenerating) — Bi-CG-STAB ρ/ω recurrence collapse, which the
            # standard solver exhibits identically, is excluded. The §Perf
            # pair G acceptance counts THIS rate.
            "sstep_basis_fallback": jnp.logical_and(
                config.sstep_s > 1, res.basis_breakdown),
            # An adaptive (Newton/Chebyshev) s-step basis failed its Gram guard
            # and the solve degraded to the monomial basis mid-stream — the
            # first link of the basis fallback chain (always False for the
            # standard solvers and the monomial basis).
            "sstep_basis_degraded": jnp.logical_and(
                config.sstep_s > 1, res.basis_degraded),
            "nc_found": res.nc_found,
            "nc_used": take_nc,
            "nc_curv": res.nc_curv,
            # λ_min(G) estimate behind the escape scale (0 when the probe did
            # not fire): Rayleigh quotient from the standard recurrences,
            # Ritz-refined per cycle on the s-step paths.
            "nc_lambda": nc_lam,
            "step_norm": tree_norm(delta_taken),
            "used_gn": state.use_gn,
            # Divergence sentinel (reject_nonfinite / strict_descent): the step
            # was rejected — params unchanged, warm start dropped, λ boosted
            # (also emitted as a `repro.obs` fault event, visible in the
            # Perfetto trace's events lane).
            "step_rejected": rejected,
        }

    # Trace-time contract: the metrics dict and the published schema move in
    # lockstep (tests/test_telemetry.py::test_metrics_contract).
    assert set(metrics) == set(METRICS_SCHEMA), sorted(
        set(metrics) ^ set(METRICS_SCHEMA))
    return new_params, new_state, metrics
