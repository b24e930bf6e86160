"""Communication-avoiding (s-step) Krylov solvers over the block backend.

The paper's Fig. 5 scaling story is gated by one synchronization per Krylov
iteration: the recurrence computes a dot product, waits for the scalar, and
only then can take the next step (α and β gate everything downstream). With
the curvature product reduced to a cheap cached linear map (PR 2), that
blocking scalar round-trip is the dominant per-iteration cost at scale — it
is pure latency, and it cannot be overlapped because the recurrence is a
strict chain through it.

The s-step (communication-avoiding) reformulation (Chronopoulos & Gear;
Hoemmen; Carson) breaks the chain: each **cycle** first grows the Krylov
space s steps ahead with a *polynomial basis* — matvecs only, no interleaved
scalars — then computes EVERY dot product the next s iterations will need as
one Gram matrix of the basis (``be.gram``: one reduction), and finally runs
the s iterations as scalar recurrences **in basis coordinates** (O(s²)
flops, zero communication). Blocking synchronizations per s iterations: one,
instead of s. The basis matvec *products* still move the same bytes, but
they form a dependency chain with no scalar gates — under the paper's
data-parallel schedule their reduces pipeline back-to-back instead of
alternating with scalar round-trips. ``benchmarks/comm_model.py`` carries
the resulting sync model (``1 + ceil(K/s) + E`` vs ``1 + K + E``) and
``benchmarks/sstep_bench.py`` measures the executed counts
(``KrylovResult.syncs``).

**The basis layer** (``BasisSpec``): the chains are generated by the
three-term recurrence  v_{j+1} = (A v_j − α_j v_j − β_j v_{j−1}) / γ_j,
and the coordinate recurrences see A only through the change-of-basis
matrix T (A·(V c) = V·(T c)) and the Gram — so the basis polynomial is
pluggable:

* ``"monomial"``  — α = β = 0, γ = 1 (the classic power chain; the extra
  axpys are skipped entirely, so this path is bit-identical to the
  historical implementation). Degenerates like the power method: κ(V)
  grows with κ(A)^s, which in f32 caps usable depth at ~4 applications
  (CG s≤4, Bi-CG-STAB s≤2 — EXPERIMENTS.md §Perf pair E).
* ``"newton"``    — shifted-Newton basis: α_j = θ_j are shifts drawn in
  deterministic magnitude-damped Leja order (``core.krylov.leja_order``)
  from a pool of the Ritz estimates plus Chebyshev points of their
  interval (``_LEJA_GRID`` — short bootstrap chains yield fewer estimates
  than deep chains need), β = 0, γ = the estimated spectral interval's
  capacity (hi−lo)/4 — the standard CA-KSM scaling that keeps
  ∏(A − θ_j I) bounded.
* ``"chebyshev"`` — scaled/shifted Chebyshev basis on the Ritz interval
  [lo, hi]: α_j = (lo+hi)/2, γ_0 = h, β_j = γ_j = h/2 for j ≥ 1 with
  h = (hi−lo)/2; |basis polynomial| ≤ 1 on the interval, the best
  conditioned of the three.

The shift/interval parameters come **for free** from Gram matrices the
solver already reduces (``core.krylov.ritz_from_segment``: the recurrence
coefficients express A on the chain exactly, so ⟨v_i, A v_j⟩ is a linear
combination of Gram entries — a small host-side-free generalized
eigenproblem via ``jnp.linalg.eigvalsh``, no extra reductions or operator
products), in two places:

* **Bootstrap cycles** (adaptive bases only): the solve opens with
  monomial cycles at the f32-safe depth (``BOOT_APPLICATIONS`` power
  applications — real iterations, not probes) until k ≥ s iterations have
  run. The iteration floor is structural, not a tuning choice: a
  full-depth cycle's 2s+1 chain vectors live in K_{k+s+1}(A, b), so
  launching one from k < s makes the joint [p-chain | r-chain] basis
  rank-deficient BY CONSTRUCTION — the coordinates random-walk in its
  null space and the f32 quadratic forms drown — no matter how good the
  polynomial is. The last bootstrap Gram yields the first Ritz estimates.
  Sync cost: ``1 + n_boot + ceil((K − covered)/s) + E`` reduces per outer
  step — still well below the monomial-best usable s (comm model:
  ``hf_sstep_syncs_per_iteration(..., basis=)``).
* **Per-cycle refresh**: every healthy adaptive cycle re-extracts Ritz
  values from its own Gram and re-parameterizes the next cycle's basis
  inside the jitted loop — the estimates track the damped operator as λ
  and the Krylov space evolve.

**Depth-resolved guard + fallback chain** (correctness never depends on a
basis surviving): adaptive cycles factorize each chain segment with a
guarded prefix Cholesky (``_prefix_guard``) and run exactly the iterations
whose chain prefix is well-conditioned — the dominant degeneration mode is
the residual running out of spectral content near convergence, where a
partial cycle is the right answer, not a discarded one. Only a basis that
cannot even START an iteration escalates: the solve *degrades* to the
monomial basis mid-stream (sticky; ``KrylovResult.basis_degraded`` records
it) → a monomial cycle fails its (binary, PR 3-semantics) guard →
breakdown, and with ``fallback=True`` the standard solver finishes from
the current iterate. ``KrylovResult.basis_breakdown`` distinguishes these
Gram-guard breakdowns from Bi-CG-STAB's intrinsic ρ/ω recurrence collapse
(which the standard solver exhibits identically).

The costs, stated honestly (EXPERIMENTS.md §Perf pairs E/G):

* **Extra operator applications.** The basis needs power chains of both the
  direction p and the residual r (they span different spaces after the first
  iteration), so a cycle performs 2s−1 (CG) / 4s−1 (Bi-CG-STAB) products for
  s iterations — asymptotically ~2× the standard recurrence's s / 2s. The
  chains advance in lock-step, so the products pair into width-2 **block
  curvature products** (``core.blocks.pair_apply``): the cached
  linearization residuals are read once per level instead of once per
  chain, clawing back much of the overhead. s-step wins exactly when the
  latency saved by s× fewer blocking syncs exceeds the extra product
  bandwidth — the paper's small-batch / many-nodes regime, where Fig. 5
  shows synchronization is what breaks scaling.
* **Basis conditioning.** Every cycle factorizes the (normalized) Gram of
  each chain segment — Cholesky, the cheapest PD certificate — and declares
  **breakdown** when a pivot collapses (or the Gram is non-finite); the
  fallback chain above handles it. The adaptive bases exist precisely to
  keep the guard quiet at depths the monomial basis cannot reach (CG s=8,
  Bi-CG-STAB s=4 — the §Perf pair G acceptance rows).
* **Memory.** A cycle keeps 2s+1 / 4s+1 model-sized basis vectors live
  (vs O(1) iterate vectors for the standard recurrences).

Both solvers return the same ``KrylovResult`` as ``core/solvers.py``, with
the same free byproducts: negative-curvature capture (the probe's dᵀAd and
dᵀd are Gram quadratic forms — literally free here) and, for Bi-CG-STAB,
φ-best tracking (⟨b,x⟩ and ⟨x,r⟩ come from three extra columns appended to
the same Gram reduction).

Backend story: everything runs on the ``BlockVectorBackend`` extension
(core/krylov.py) — "tree" keeps the basis as a stacked pytree
(sharding-preserving Gram via per-leaf contractions + one small all-reduce),
"flat" stacks rows into an (s, n) matrix and computes the Gram with the
fused Pallas ``dots_block`` kernel (one pass over the stacked data). The
basis recurrences are backend ops (axpy/scale), so every basis runs on both.

**Overlapped (double-buffered) cycles** (``overlap=True``): the coordinate
recurrences only consume the Gram at cycle end, so two consecutive cycles
can share one reduction — each super-cycle grows the chains through BOTH
cycles' spans (depth 2s) back-to-back before any Gram-gated scalar exists,
issues ONE Gram whose all-reduce is thereby hidden behind the second
cycle's chain growth, and runs 2s iterations of coordinate recurrences
from it. Blocking synchronizations per outer step drop from
``1 + ceil(K/s) + E`` to ``ceil(K/2s) + ceil(E/2)`` (the comm model's
``overlap=True`` series). The second cycle is SPECULATIVE: unlike simply
doubling s (whose binary monomial guard discards the whole cycle when the
deep half degenerates), overlapped cycles always run under the
depth-resolved prefix guard — the deep half executes exactly as far as its
chain prefix stays well-conditioned and is abandoned at zero cost
otherwise, so ``overlap=True`` at a given s never converges worse than the
non-overlapped s it doubles up. Monomial overlap is f32-viable at s=2
(depth-4 chains, the ``BOOT_APPLICATIONS`` budget); the Newton/Chebyshev
bases carry s=4 (CG, depth-8 chains — the §Perf pair G validated depth).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from .blocks import pair_apply
from .krylov import (
    EPS as _EPS,
    NCState,
    best_init,
    BestState,
    guard_div,
    leja_order,
    nc_init,
    ritz_from_segment,
)
from .solvers import KrylovResult, bicgstab, cg, _resolve
from .tree_math import tree_where

Op = Callable[[Any], Any]

# Breakdown threshold on the *normalized* Gram's Cholesky pivots: a pivot of
# p means the newest basis vector is only p away (in relative norm) from the
# span of the previous ones, so coordinate round-off is amplified by ~1/p.
# 1e-4 keeps f32 cycles that still converge cleanly (measured: depth-4/5
# chains on moderately conditioned systems sit at 2e-4..4e-3 and recover the
# standard solution to 1e-7) while catching the genuinely degenerate bases
# (deep chains / ill-conditioned operators collapse to <1e-7 or NaN).
GUARD_PIVOT = 1e-4

# f32 monomial depth budget: the deepest power chain that reliably passes
# the Gram guard (EXPERIMENTS.md §Perf pair E — CG s≤4 ⇒ 4 applications;
# Bi-CG-STAB applies A twice per iteration, so its budget is s=2 ⇒ the same
# 4-deep chains). The adaptive bases' bootstrap cycle runs the monomial
# basis at this depth to harvest the first Ritz estimates.
BOOT_APPLICATIONS = 4

BASES = ("monomial", "newton", "chebyshev")

# Newton-shift candidate pool: the Ritz estimates plus this many Chebyshev
# points of the Ritz interval; the chain takes the first `depth` candidates
# in Leja order. Estimating from a short bootstrap chain yields fewer Ritz
# values than deep chains need — interval Leja points fill the gap with the
# asymptotically optimal shift distribution instead of repeating a handful
# of estimates (repeated shifts square the Newton polynomial and degrade
# the basis conditioning measurably).
_LEJA_GRID = 32

# Chebyshev interval widening: each end of the Ritz interval is pushed out
# by this fraction of its width. A short chain's extreme Ritz values
# underestimate the true spectral edges, and a Chebyshev basis grows
# EXPONENTIALLY outside its interval — overshooting costs a little
# effective degree, undershooting costs the basis (the asymmetric risk the
# widening prices in).
_CHEB_WIDEN = 0.1


@dataclasses.dataclass(frozen=True)
class BasisSpec:
    """Polynomial basis for the s-step chains (see module docstring).

    ``kind`` ∈ {"monomial", "newton", "chebyshev"}. The adaptive kinds
    (newton/chebyshev) bootstrap from a short monomial cycle and refresh
    their shift/interval parameters from each cycle's Gram.
    """

    kind: str = "monomial"

    def __post_init__(self):
        if self.kind not in BASES:
            raise ValueError(
                f"sstep basis must be one of {BASES}, got {self.kind!r}"
            )

    @property
    def adaptive(self) -> bool:
        return self.kind != "monomial"

    def coeffs(self, ritz, ok, depth: int):
        """Three-term-recurrence coefficient arrays (α, β, γ), each of
        length ``depth``, from Ritz estimates ``ritz`` (ascending). When
        ``ok`` is False the arrays collapse to the monomial values
        (0, 0, 1) — the estimate is unusable and the chain must stay
        guard-protected rather than scaled by garbage."""
        f32 = jnp.float32
        if self.kind == "monomial":
            return (jnp.zeros((depth,), f32), jnp.zeros((depth,), f32),
                    jnp.ones((depth,), f32))
        lo, hi = jnp.min(ritz), jnp.max(ritz)
        # Scale floor: a degenerate spectrum (hi ≈ lo) gives a zero
        # interval; the floored γ keeps the recurrence finite and leaves
        # the Gram guard (not a division) to rule on the basis.
        floor = 1e-6 * (1.0 + jnp.abs(lo) + jnp.abs(hi))
        if self.kind == "newton":
            # Shift pool: Ritz values + Chebyshev points of their interval,
            # consumed in Leja order (see _LEJA_GRID).
            kk = jnp.arange(_LEJA_GRID, dtype=f32)
            grid = 0.5 * (lo + hi) + 0.5 * (hi - lo) * jnp.cos(
                (2.0 * kk + 1.0) * (jnp.pi / (2 * _LEJA_GRID)))
            th = leja_order(jnp.concatenate([ritz.astype(f32), grid]))
            alpha = th[:depth]
            beta = jnp.zeros((depth,), f32)
            # Capacity of [lo, hi] — the scaling that bounds ∏(A − θ_j I).
            gamma = jnp.full((depth,), jnp.maximum((hi - lo) / 4.0, floor),
                             f32)
        else:  # chebyshev
            c = 0.5 * (lo + hi)
            h = jnp.maximum(
                (0.5 + _CHEB_WIDEN) * (hi - lo), floor)
            alpha = jnp.full((depth,), c, f32)
            beta = jnp.concatenate(
                [jnp.zeros((1,), f32), jnp.full((depth - 1,), 0.5 * h, f32)])
            gamma = jnp.concatenate(
                [jnp.full((1,), h, f32), jnp.full((depth - 1,), 0.5 * h, f32)])
        alpha = jnp.where(ok, alpha, 0.0).astype(f32)
        beta = jnp.where(ok, beta, 0.0).astype(f32)
        gamma = jnp.where(ok, gamma, 1.0).astype(f32)
        return alpha, beta, gamma


def resolve_basis(basis: Union[str, BasisSpec, None]) -> BasisSpec:
    """Accept a kind string, a BasisSpec, or None (⇒ monomial)."""
    if basis is None:
        return BasisSpec("monomial")
    if isinstance(basis, BasisSpec):
        return basis
    return BasisSpec(str(basis))


def _shift(segments) -> jax.Array:
    """Static change-of-basis matrix T for a concatenation of *monomial*
    power chains: A·(V c) = V·(T c). Within each segment T maps
    e_j ↦ e_{j+1}; the last column of each segment is zero (the recurrences
    never reach it — that is precisely the s-iterations-per-cycle budget)."""
    m = sum(segments)
    T = np.zeros((m, m), np.float32)
    start = 0
    for seg in segments:
        for j in range(seg - 1):
            T[start + j + 1, start + j] = 1.0
        start += seg
    return jnp.asarray(T)


def _segment_shift(L: int) -> jax.Array:
    """Static (L, L−1) monomial recurrence block: A v_j = v_{j+1}."""
    T = np.zeros((L, L - 1), np.float32)
    for j in range(L - 1):
        T[j + 1, j] = 1.0
    return jnp.asarray(T)


def _segment_T(coeffs, L: int) -> jax.Array:
    """(L, L−1) recurrence block for one chain under traced basis coeffs:
    column j holds the coordinates of A v_j —
    A v_j = β_j v_{j−1} + α_j v_j + γ_j v_{j+1}."""
    alpha, beta, gamma = coeffs
    T = jnp.zeros((L, L - 1), jnp.float32)
    for j in range(L - 1):
        T = T.at[j, j].set(alpha[j])
        T = T.at[j + 1, j].set(gamma[j])
        if j > 0:
            T = T.at[j - 1, j].set(beta[j])
    return T


def _basis_T(coeffs, segments) -> jax.Array:
    """Traced change-of-basis matrix for a concatenation of chains sharing
    one three-term recurrence: block-diagonal ``_segment_T`` blocks, last
    column of each segment zero (same contract as ``_shift``)."""
    m = sum(segments)
    T = jnp.zeros((m, m), jnp.float32)
    start = 0
    for seg in segments:
        T = T.at[start:start + seg, start:start + seg - 1].set(
            _segment_T(coeffs, seg))
        start += seg
    return T


def _grow_chains(be, pair, A_, p, r, n_apps: int, coeffs):
    """Grow the paired p/r polynomial chains: ``n_apps`` recurrence
    applications for the p-chain (n_apps+1 vectors), n_apps−1 for the
    r-chain (n_apps vectors) — the chains advance in lock-step as width-2
    block products (``pair``), with the p-chain's extra last level a single
    application. ``coeffs = (α, β, γ)`` drive
    v_{j+1} = (A v_j − α_j v_j − β_j v_{j−1}) / γ_j; None ⇒ monomial power
    chain (the combine axpys are skipped entirely — bit-identical to the
    historical path)."""
    if coeffs is None:
        def adv(w, v, vp, j):
            return w
    else:
        alpha, beta, gamma = coeffs
        inv_g = 1.0 / gamma

        def adv(w, v, vp, j):
            t = be.axpy(-alpha[j], v, w)
            t = be.axpy(-beta[j], vp, t)
            return be.scale(inv_g[j], t)

    pch, rch = [p], [r]
    for j in range(n_apps - 1):
        w, u = pair(pch[-1], rch[-1])
        pnew = adv(w, pch[-1], pch[-2] if j else pch[-1], j)
        rnew = adv(u, rch[-1], rch[-2] if j else rch[-1], j)
        pch.append(pnew)
        rch.append(rnew)
    j = n_apps - 1
    w = A_(pch[-1])
    pch.append(adv(w, pch[-1], pch[-2] if j else pch[-1], j))
    return pch, rch


def _onehot(m: int, j: int) -> jax.Array:
    return jnp.zeros((m,), jnp.float32).at[j].set(1.0)


def _gram_ok(G, segments, guard_pivot: float) -> jax.Array:
    """Binary basis-conditioning guard on the Gram factorization (the
    monomial chains' all-or-nothing form — PR 3 semantics).

    Normalizes G to a correlation matrix (so near-converged tiny residual
    chains are not flagged for scale alone) and Cholesky-factorizes each
    power segment separately — across-segment rank deficiency is legitimate
    (first cycle has p ≡ r, so the two chains coincide exactly) while
    within-segment pivot collapse is the basis-degeneracy signal.
    """
    d = jnp.sqrt(jnp.clip(jnp.diagonal(G), 0.0))
    dn = 1.0 / jnp.maximum(d, _EPS)
    Gn = G * jnp.outer(dn, dn)
    ok = jnp.all(jnp.isfinite(G))
    start = 0
    for seg in segments:
        L = jnp.linalg.cholesky(Gn[start:start + seg, start:start + seg])
        piv = jnp.diagonal(L)
        ok = jnp.logical_and(
            ok,
            jnp.logical_and(jnp.all(jnp.isfinite(L)), jnp.min(piv) > guard_pivot),
        )
        start += seg
    return ok


def _chol_prefix_ok(Gn, guard_pivot: float) -> jax.Array:
    """Per-column prefix-conditioning flags of a normalized segment Gram.

    Unrolled guarded Cholesky (the segment is ≤ 17×17): ``ok[i]`` says the
    leading (i+1)-column block factorizes with every pivot above
    ``guard_pivot`` — i.e. the chain is usable THROUGH column i even when
    deeper columns have degenerated. ``jnp.linalg.cholesky`` cannot provide
    this (on failure it NaNs the whole factor); the guarded unroll keeps
    the valid leading pivots and makes the flags sticky-false afterwards
    (non-finite or collapsed pivots poison every deeper flag).
    """
    L = Gn.shape[0]
    thresh = guard_pivot * guard_pivot
    M = Gn
    idx = jnp.arange(L)
    good = jnp.ones((), bool)
    ok = []
    for j in range(L):
        pivsq = M[j, j]
        good = jnp.logical_and(
            good, jnp.logical_and(jnp.isfinite(pivsq), pivsq > thresh))
        ok.append(good)
        piv = jnp.sqrt(jnp.maximum(pivsq, _EPS))
        c = jnp.where(idx > j, M[:, j], 0.0) / piv
        M = M - jnp.outer(c, c)
    return jnp.stack(ok)


def _prefix_guard(G, segments, guard_pivot: float):
    """Depth-resolving guard for the adaptive bases: returns
    ``(finite, [per-segment prefix-ok arrays])``.

    Where the monomial guard discards a whole cycle, the adaptive bases
    only give up the iterations whose chain prefix has degenerated: the
    dominant degeneration mode is the residual running out of spectral
    content NEAR CONVERGENCE, where the leading chain columns are still
    perfectly usable — a partial cycle converges or hands a shorter step
    to the next cycle's fresh Gram, and the fallback chain is reserved for
    bases that cannot even start (see the cycle bodies).
    """
    d = jnp.sqrt(jnp.clip(jnp.diagonal(G), 0.0))
    dn = 1.0 / jnp.maximum(d, _EPS)
    Gn = G * jnp.outer(dn, dn)
    finite = jnp.all(jnp.isfinite(G))
    oks = []
    start = 0
    for seg in segments:
        oks.append(_chol_prefix_ok(
            Gn[start:start + seg, start:start + seg], guard_pivot))
        start += seg
    return finite, oks


def _lam_floor(nc_lam, ritz, rok, lam):
    """Fold a cycle's minimum Ritz value (of the DAMPED operator A, shifted
    by −λ back to the raw G) into the running λ_min estimate. Free: the
    Ritz values come from a Gram the cycle already reduced, and a Ritz
    value lower-bounds the probe's Rayleigh quotient along any chain
    direction — the tighter |λ| scale for the saddle-free escape step
    (``KrylovResult.nc_lambda``, HFConfig.nc_mode="escape")."""
    return jnp.minimum(nc_lam, jnp.where(rok, jnp.min(ritz) - lam, 0.0))


def _merge_fallback(res: KrylovResult, run_standard) -> KrylovResult:
    """On basis breakdown, hand the iterate to the standard solver (traced
    into the other ``lax.cond`` branch — it only executes on breakdown) and
    merge the byproducts: the most-negative NC direction wins, iteration and
    sync counts accumulate, and ``breakdown=True`` records that the fallback
    ran."""
    def fb(r):
        std = run_standard(r.x)
        std_better = std.nc_curv < r.nc_curv
        # Residual curve: the partial s-step prefix, then the standard
        # solve's curve shifted to start at iteration r.iters (entries past
        # the fixed buffer are dropped; the roll's wrap-around lands only
        # on positions the prefix already owns).
        idx = jnp.arange(r.residual_history.shape[0])
        hist = jnp.where(idx < r.iters, r.residual_history,
                         jnp.roll(std.residual_history, r.iters))
        return KrylovResult(
            std.x, std.r, std.x_best, std.r_best,
            tree_where(std_better, std.nc_dir, r.nc_dir),
            jnp.logical_or(std.nc_found, r.nc_found),
            jnp.minimum(std.nc_curv, r.nc_curv),
            r.iters + std.iters, std.residual,
            syncs=r.syncs + std.syncs, breakdown=jnp.ones((), bool),
            basis_degraded=r.basis_degraded,
            basis_breakdown=r.basis_breakdown,
            residual_history=hist,
            nc_lambda=jnp.minimum(std.nc_lambda, r.nc_lambda),
        )

    return jax.lax.cond(res.breakdown, fb, lambda r: r, res)


def sstep_cg(A: Op, b, x0, *, lam, s: int, max_iters: int, tol: float = 5e-3,
             backend=None, A_block: Optional[Op] = None,
             fallback: bool = True,
             guard_pivot: float = GUARD_PIVOT,
             basis: Union[str, BasisSpec] = "monomial",
             overlap: bool = False) -> KrylovResult:
    """s-step CG with Martens truncation and free negative-curvature capture.

    Mathematically iteration-for-iteration identical to ``solvers.cg`` (in
    exact arithmetic): each cycle builds the polynomial basis
    [p-chain | r-chain] for the ``basis`` spec, reduces its Gram ONCE, and
    runs s CG steps in coordinates. ``A_block`` (optional) applies the
    operator to a stacked pair per chain level. Adaptive bases
    (newton/chebyshev) bootstrap with one monomial cycle at the f32-safe
    depth and refresh their Ritz parameters from each cycle's Gram;
    ``fallback`` re-enters ``solvers.cg`` from the current iterate if the
    monomial (or degraded-to-monomial) Gram factorization flags the basis.

    ``overlap=True`` runs double-buffered super-cycles: two consecutive
    cycles share one Gram reduction (chains grown 2s deep back-to-back, the
    reduce hidden behind the second cycle's growth), with the speculative
    deep half under the depth-resolved prefix guard — see the module
    docstring. ``KrylovResult.syncs`` counts Gram reductions either way, so
    overlap halves it for the same iteration budget.
    """
    spec = resolve_basis(basis)
    s_run = 2 * s if overlap else s
    be = _resolve(backend)
    A_ = be.wrap_op(A)
    Ab_ = None if A_block is None else be.wrap_block_op(A_block)
    pair = pair_apply(be, A_, Ab_)
    b_ = be.lift(b)
    x0_ = be.lift(x0)
    b_norm = be.norm(b_)
    r0 = be.sub(b_, A_(x0_))
    rr0 = be.dot(r0, r0)

    def cycle(x, r, p, k, nc, s_cyc, coeffs, stop0, hist):
        """One cycle of up to ``s_cyc`` CG iterations over the polynomial
        basis generated by ``coeffs`` (None ⇒ monomial). Returns the
        advanced carries plus the cycle's Gram for Ritz extraction."""
        m = 2 * s_cyc + 1
        segs = (s_cyc + 1, s_cyc)
        T = _shift(segs) if coeffs is None else _basis_T(coeffs, segs)
        e_p, e_r = _onehot(m, 0), _onehot(m, s_cyc + 1)
        # ---- grow the space s steps ahead: matvecs only, no scalar gates --
        pch, rch = _grow_chains(be, pair, A_, p, r, s_cyc, coeffs)
        V = be.block_stack(pch + rch)
        # ---- the cycle's ONE reduction: every dot for s iterations --------
        G = be.gram(V, V)
        G = 0.5 * (G + G.T)
        if coeffs is None:
            # Monomial: binary whole-cycle guard (PR 3 semantics).
            brk = jnp.logical_not(_gram_ok(G, segs, guard_pivot))
            ok_iter = [jnp.ones((), bool)] * s_cyc
        else:
            # Adaptive: depth-resolved guard — iteration j touches p-chain
            # columns ≤ j+1 and r-chain columns ≤ j, so it may run exactly
            # when those prefixes factorize cleanly. brk only when the
            # basis cannot even start (→ degrade chain in the driver).
            finite, (okp, okr) = _prefix_guard(G, segs, guard_pivot)
            ok_iter = [
                jnp.logical_and(finite,
                                jnp.logical_and(okp[j + 1], okr[j]))
                for j in range(s_cyc)
            ]
            brk = jnp.logical_not(ok_iter[0])

        # ---- s CG iterations as O(s²) coordinate recurrences --------------
        p_c, r_c = e_p, e_r
        x_c = jnp.zeros((m,), jnp.float32)
        rr_c = G[s_cyc + 1, s_cyc + 1]
        stop = jnp.logical_or(brk, stop0)  # masks this cycle's iterations
        term = jnp.zeros((), bool)         # solve-level stop: trunc / conv
        it = jnp.zeros((), jnp.int32)
        cyc_found = jnp.zeros((), bool)
        cyc_curv = nc.curv
        cyc_imp = jnp.zeros((), bool)
        nc_c = jnp.zeros((m,), jnp.float32)
        for j in range(s_cyc):
            active = jnp.logical_and(
                jnp.logical_and(jnp.logical_not(stop), k + j < max_iters),
                ok_iter[j])
            Tp = T @ p_c
            pAp = p_c @ (G @ Tp)
            p_sq = p_c @ (G @ p_c)
            # NC probe — the (dᵀAd, dᵀd) pair is two Gram quadratic forms
            raw = (pAp - lam * p_sq) / jnp.maximum(p_sq, _EPS)
            is_nc = jnp.logical_and(active, raw < 0.0)
            better = jnp.logical_and(is_nc, raw < cyc_curv)
            nc_c = jnp.where(
                better, p_c / jnp.sqrt(jnp.maximum(p_sq, _EPS)), nc_c
            )
            cyc_curv = jnp.where(better, raw, cyc_curv)
            cyc_imp = jnp.logical_or(cyc_imp, better)
            cyc_found = jnp.logical_or(cyc_found, is_nc)
            # Martens truncation — same freeze semantics as solvers._cg_engine
            trunc = pAp <= _EPS
            step_ok = jnp.logical_and(active, jnp.logical_not(trunc))
            alpha = rr_c / jnp.maximum(pAp, _EPS)
            x_new = x_c + alpha * p_c
            r_new = r_c - alpha * Tp
            rr_new = r_new @ (G @ r_new)
            # f32 coordinate-drift guard: ⟨r,r⟩ computed as a Gram quadratic
            # form can round to ≤ 0 when the coordinates outgrow the basis's
            # representable accuracy. Discard the step and end the CYCLE —
            # the next cycle restarts the recurrence from a fresh Gram of
            # the materialized iterate — and never report it as convergence.
            rr_bad = rr_new <= 0.0
            good = jnp.logical_and(step_ok, jnp.logical_not(rr_bad))
            beta = rr_new / jnp.maximum(rr_c, _EPS)
            p_new = r_new + beta * p_c
            x_c = jnp.where(good, x_new, x_c)
            r_c = jnp.where(good, r_new, r_c)
            p_c = jnp.where(good, p_new, p_c)
            rr_c = jnp.where(good, rr_new, rr_c)
            # Residual curve at absolute iteration k + it (coordinate-form
            # ⟨r,r⟩, already reduced in the Gram — no extra collective).
            hist = jnp.where(
                active,
                hist.at[k + it].set(jnp.sqrt(jnp.maximum(rr_c, 0.0))),
                hist)
            it = it + active.astype(jnp.int32)
            conv = jnp.logical_and(
                rr_c > 0.0, jnp.sqrt(jnp.maximum(rr_c, 0.0)) < tol * b_norm)
            ev = jnp.logical_or(jnp.logical_and(active, trunc),
                                jnp.logical_and(good, conv))
            if j == 0:
                # Stagnation: drift-guard failure on a cycle's FIRST step
                # leaves the carries bit-identical, so the next cycle would
                # rebuild the same chains and fail the same way — burning
                # the budget one iteration per Gram. End the solve; the
                # iterate stands (numerically exhausted in f32 forms).
                ev = jnp.logical_or(ev, jnp.logical_and(step_ok, rr_bad))
            term = jnp.logical_or(term, ev)
            stop = jnp.logical_or(
                stop, jnp.logical_or(ev, jnp.logical_and(step_ok, rr_bad)))

        # ---- materialize the cycle: one combined pass over the basis ------
        # On basis breakdown the coords are still the one-hot inits, but the
        # overflowed basis may hold inf (0·inf = NaN in the combine) — keep
        # the carried vectors instead.
        out = be.block_combine(jnp.stack([x_c, r_c, p_c, nc_c]), V)
        x2 = be.where(brk, x, be.axpy(1.0, be.block_col(out, 0), x))
        r2 = be.where(brk, r, be.block_col(out, 1))
        p2 = be.where(brk, p, be.block_col(out, 2))
        nc2 = NCState(
            jnp.logical_or(nc.found, cyc_found),
            be.where(cyc_imp, be.block_col(out, 3), nc.dir),
            jnp.where(cyc_imp, cyc_curv, nc.curv),
        )
        return x2, r2, p2, rr_c, it, term, brk, nc2, hist, G

    done0 = jnp.sqrt(rr0) < tol * b_norm
    hist0 = jnp.full((max_iters,), jnp.nan, jnp.float32)
    nc0 = nc_init(be, b_)
    zero_i = jnp.zeros((), jnp.int32)
    no = jnp.zeros((), bool)

    if not spec.adaptive:
        # Overlapped monomial super-cycles pass explicit monomial coeff
        # ARRAYS (not None) so the cycle runs the depth-resolved prefix
        # guard: the speculative second half executes only as deep as its
        # chain prefix stays conditioned — a deep-half failure costs a
        # partial cycle, not a breakdown (the binary whole-cycle guard
        # would discard all 2s iterations).
        cf_mono = None
        if overlap:
            cf_mono = (jnp.zeros((s_run,), jnp.float32),
                       jnp.zeros((s_run,), jnp.float32),
                       jnp.ones((s_run,), jnp.float32))

        def cond(carry):
            (_, _, _, _, k, done, _, _, _, _, _) = carry
            return jnp.logical_and(k < max_iters, jnp.logical_not(done))

        def body(carry):
            x, r, p, rr, k, done, brk0, nc, syncs, hist, nc_lam = carry
            x, r, p, rr, it, term, brk, nc, hist, G = cycle(
                x, r, p, k, nc, s_run, cf_mono, no, hist)
            # Free λ_min estimate from the monomial cycle's Gram: the power
            # chain's recurrence block is the static shift matrix, so the
            # same ritz_from_segment extraction the adaptive bases use
            # applies — no extra reductions or operator products.
            ritz, rok = ritz_from_segment(
                G[:s_run + 1, :s_run + 1], _segment_shift(s_run + 1))
            nc_lam = _lam_floor(nc_lam, ritz, rok, lam)
            return (x, r, p, rr, k + it, jnp.logical_or(term, brk),
                    jnp.logical_or(brk0, brk), nc, syncs + 1, hist, nc_lam)

        init = (x0_, r0, r0, rr0, zero_i, done0, no, nc0, zero_i, hist0,
                jnp.zeros((), jnp.float32))
        x, r, _, rr, k, _, brk, nc, syncs, hist, nc_lam = jax.lax.while_loop(
            cond, body, init)
        degraded = no
    else:
        # ---- bootstrap: monomial cycles at the f32-safe depth -------------
        # Real iterations (not a probe), run until k ≥ s: the first
        # full-depth cycle's 2s+1 chain vectors live in K_{k+s+1}(A, b), so
        # launching it from k < s makes the joint basis STRUCTURALLY
        # rank-deficient (coordinates random-walk in the null space and f32
        # quadratic forms drown) no matter how good the polynomial is. The
        # last bootstrap Gram yields the first Ritz estimates for free.
        s_boot = max(1, min(s_run, BOOT_APPLICATIONS))
        n_boot = -(-s_run // s_boot)       # ceil(s_run / s_boot) cycles
        # Monomial coefficient ARRAYS (not None): the bootstrap cycles run
        # under the depth-resolved prefix guard, so a warm start that is
        # already nearly converged (degenerate shallow chains) runs partial
        # cycles instead of aborting the whole adaptive solve.
        cf0 = (jnp.zeros((s_boot,), jnp.float32),
               jnp.zeros((s_boot,), jnp.float32),
               jnp.ones((s_boot,), jnp.float32))
        xb, rb, pb = x0_, r0, r0
        rrb, kb, ncb = rr0, zero_i, nc0
        termb, brkb, Gb = done0, no, None
        histb = hist0
        for _ in range(n_boot):
            # A cycle only speaks for the solve while the solve is LIVE:
            # once terminated (converged/truncated) the remaining unrolled
            # bootstrap cycles still trace chains from the stale residual,
            # whose degenerate Gram must not be read as a breakdown.
            live = jnp.logical_not(jnp.logical_or(termb, brkb))
            xb, rb, pb, rrb, it_, term_, brk_, ncb, histb, Gb = cycle(
                xb, rb, pb, kb, ncb, s_boot, cf0,
                jnp.logical_not(live), histb)
            kb = kb + it_
            termb = jnp.logical_or(termb, term_)
            brkb = jnp.logical_or(brkb, jnp.logical_and(brk_, live))
        ritz, rok = ritz_from_segment(
            Gb[:s_boot + 1, :s_boot + 1], _segment_shift(s_boot + 1))
        al, bt, gm = spec.coeffs(ritz, rok, s_run)
        nc_lam0 = _lam_floor(jnp.zeros((), jnp.float32), ritz, rok, lam)

        def cond(carry):
            return jnp.logical_and(carry[4] < max_iters,
                                   jnp.logical_not(carry[5]))

        def body(carry):
            (x, r, p, rr, k, done, brk0, nc, syncs,
             al, bt, gm, adaptive, degraded, hist, nc_lam) = carry
            xn, rn, pn, rrn, it, term, brk, ncn, hist, G = cycle(
                x, r, p, k, nc, s_run, (al, bt, gm), no, hist)
            # Fallback chain: adaptive-basis guard failure degrades the
            # solve to the monomial basis (sticky; the failed cycle is
            # frozen and discarded); a monomial failure is the hard
            # breakdown the standard-solver fallback handles.
            degrade = jnp.logical_and(brk, adaptive)
            hard = jnp.logical_and(brk, jnp.logical_not(adaptive))
            # Free per-cycle refresh: Ritz values of A on this cycle's
            # p-chain, from the Gram just reduced.
            ritz, rok = ritz_from_segment(
                G[:s_run + 1, :s_run + 1], _segment_T((al, bt, gm), s_run + 1))
            nc_lam = _lam_floor(nc_lam, ritz, rok, lam)
            fa, fb_, fg = spec.coeffs(ritz, rok, s_run)
            refresh = jnp.logical_and(
                adaptive, jnp.logical_and(rok, jnp.logical_not(brk)))
            al2 = jnp.where(degrade, 0.0, jnp.where(refresh, fa, al))
            bt2 = jnp.where(degrade, 0.0, jnp.where(refresh, fb_, bt))
            gm2 = jnp.where(degrade, 1.0, jnp.where(refresh, fg, gm))
            return (xn, rn, pn, rrn, k + it,
                    jnp.logical_or(term, hard),
                    jnp.logical_or(brk0, hard), ncn, syncs + 1,
                    al2, bt2, gm2,
                    jnp.logical_and(adaptive, jnp.logical_not(degrade)),
                    jnp.logical_or(degraded, degrade), hist, nc_lam)

        init = (xb, rb, pb, rrb, kb, jnp.logical_or(termb, brkb), brkb, ncb,
                jnp.asarray(n_boot, jnp.int32), al, bt, gm,
                jnp.logical_and(rok, jnp.logical_not(brkb)), no, histb,
                nc_lam0)
        (x, r, _, rr, k, _, brk, nc, syncs,
         _, _, _, _, degraded, hist, nc_lam) = jax.lax.while_loop(
            cond, body, init)

    x, r, nc_dir = be.lower(x), be.lower(r), be.lower(nc.dir)
    # Every CG-side breakdown is a Gram-guard (basis) event — the CG
    # recurrence itself has no ρ/ω-style collapse.
    res = KrylovResult(x, r, x, r, nc_dir, nc.found, nc.curv, k,
                       jnp.sqrt(jnp.maximum(rr, 0.0)),
                       syncs=syncs, breakdown=brk, basis_degraded=degraded,
                       basis_breakdown=brk, residual_history=hist,
                       nc_lambda=jnp.minimum(nc_lam, nc.curv))
    if not fallback:
        return res
    return _merge_fallback(
        res,
        lambda xs: cg(A, b, xs, lam=lam, max_iters=max_iters, tol=tol,
                      backend=backend),
    )


def sstep_bicgstab(A: Op, b, x0, *, lam, s: int, max_iters: int,
                   tol: float = 5e-3, backend=None,
                   A_block: Optional[Op] = None,
                   fallback: bool = True,
                   guard_pivot: float = GUARD_PIVOT,
                   basis: Union[str, BasisSpec] = "monomial",
                   overlap: bool = False) -> KrylovResult:
    """s-step Bi-CG-STAB (CA-BICGSTAB, Carson) with NC capture and φ-best.

    Each cycle builds [p-chain, r-chain] 2s deep (an iteration applies A
    twice), appends three probe columns [r0*, b, x] to the Gram's right
    operand — ⟨·,r0*⟩ drives ρ/α, ⟨·,b⟩ and ⟨·,x⟩ make the φ-best tracker
    free — and reduces everything in ONE ``be.gram`` call. The ``basis``
    spec generates the chains (monomial, or Ritz-parameterized
    shifted-Newton/Chebyshev — bootstrapped and refreshed as in
    ``sstep_cg``; the deeper 2s chains are exactly where the adaptive bases
    pay, keeping the indefinite-Hessian solver viable at s=4). Breakdown
    covers both the Gram-factorization guard (adaptive → monomial →
    standard chain) and ``solvers.bicgstab``'s ρ/ω collapse (which freezes
    the iterate, like the standard solver, and is reported in
    ``KrylovResult.breakdown``); with ``fallback`` either hard kind
    re-enters the standard solver from the current iterate — for ρ/ω
    collapse that restart draws a fresh shadow residual r0*, the classic
    recovery.

    ``overlap=True``: double-buffered super-cycles — two cycles per Gram
    reduction (chains 4s deep), speculative deep half under the
    depth-resolved prefix guard (see ``sstep_cg`` / module docstring).
    """
    spec = resolve_basis(basis)
    s_run = 2 * s if overlap else s
    be = _resolve(backend)
    A_ = be.wrap_op(A)
    Ab_ = None if A_block is None else be.wrap_block_op(A_block)
    pair = pair_apply(be, A_, Ab_)
    b_ = be.lift(b)
    x0_ = be.lift(x0)
    b_norm = be.norm(b_)
    r0 = be.sub(b_, A_(x0_))
    r0_star = r0
    rn0 = be.norm(r0)
    bx0 = be.dot(b_, x0_)

    def cycle(x, r, p, bx, k, nc, best, s_cyc, coeffs, stop0, hist):
        """One cycle of up to ``s_cyc`` Bi-CG-STAB iterations in basis
        coordinates. Returns advanced carries + the cycle's Gram."""
        m = 4 * s_cyc + 1
        segs = (2 * s_cyc + 1, 2 * s_cyc)
        T = _shift(segs) if coeffs is None else _basis_T(coeffs, segs)
        e_p, e_r = _onehot(m, 0), _onehot(m, 2 * s_cyc + 1)
        # ---- power chains, 2s deep (two A-applications per iteration) -----
        pch, rch = _grow_chains(be, pair, A_, p, r, 2 * s_cyc, coeffs)
        cols = pch + rch
        V = be.block_stack(cols)
        W = be.block_stack(cols + [r0_star, b_, x])
        # ---- ONE reduction: basis Gram + the r0*/b/x probe columns --------
        Ge = be.gram(V, W)
        G = 0.5 * (Ge[:, :m] + Ge[:, :m].T)
        g_r0, g_b, g_x0 = Ge[:, m], Ge[:, m + 1], Ge[:, m + 2]
        if coeffs is None:
            # Monomial: binary whole-cycle guard (PR 3 semantics).
            brk_basis = jnp.logical_not(_gram_ok(G, segs, guard_pivot))
            ok_iter = [jnp.ones((), bool)] * s_cyc
        else:
            # Adaptive: depth-resolved guard — a Bi-CG-STAB iteration
            # applies T twice, touching p-chain columns ≤ 2j+2 and r-chain
            # columns ≤ 2j+1.
            finite, (okp, okr) = _prefix_guard(G, segs, guard_pivot)
            ok_iter = [
                jnp.logical_and(
                    finite,
                    jnp.logical_and(okp[2 * j + 2], okr[2 * j + 1]))
                for j in range(s_cyc)
            ]
            brk_basis = jnp.logical_not(ok_iter[0])

        # ---- s Bi-CG-STAB iterations in coordinates -----------------------
        p_c, r_c = e_p, e_r
        x_c = jnp.zeros((m,), jnp.float32)
        rho = g_r0[2 * s_cyc + 1]
        rr_c = G[2 * s_cyc + 1, 2 * s_cyc + 1]
        stop = jnp.logical_or(brk_basis, stop0)  # masks this cycle
        term = jnp.zeros((), bool)               # solve-level: convergence
        it = jnp.zeros((), jnp.int32)
        brk_rec = jnp.zeros((), bool)
        cyc_found = jnp.zeros((), bool)
        cyc_curv = nc.curv
        cyc_imp = jnp.zeros((), bool)
        nc_c = jnp.zeros((m,), jnp.float32)
        best_xc = jnp.zeros((m,), jnp.float32)
        best_rc = jnp.zeros((m,), jnp.float32)
        best_phi = best.phi
        best_imp = jnp.zeros((), bool)

        def probe(active, cand_c, quad, sq, state):
            nc_c, cyc_curv, cyc_imp, cyc_found = state
            raw = (quad - lam * sq) / jnp.maximum(sq, _EPS)
            is_nc = jnp.logical_and(active, raw < 0.0)
            better = jnp.logical_and(is_nc, raw < cyc_curv)
            nc_c = jnp.where(
                better, cand_c / jnp.sqrt(jnp.maximum(sq, _EPS)), nc_c
            )
            return (nc_c, jnp.where(better, raw, cyc_curv),
                    jnp.logical_or(cyc_imp, better),
                    jnp.logical_or(cyc_found, is_nc))

        for j in range(s_cyc):
            active = jnp.logical_and(
                jnp.logical_and(jnp.logical_not(stop), k + j < max_iters),
                ok_iter[j])
            v_c = T @ p_c                                    # A p̂_j
            Gv = G @ v_c
            pAp = p_c @ Gv
            p_sq = p_c @ (G @ p_c)
            nc_state = probe(active, p_c, pAp, p_sq,
                             (nc_c, cyc_curv, cyc_imp, cyc_found))
            alpha, bka = guard_div(rho, v_c @ g_r0)
            s_c = r_c - alpha * v_c                          # ŝ_j
            t_c = T @ s_c                                    # A ŝ_j
            Gt = G @ t_c
            ts = s_c @ Gt
            ss = s_c @ (G @ s_c)
            nc_c, cyc_curv, cyc_imp, cyc_found = probe(
                active, s_c, ts, ss, nc_state)
            tt = t_c @ Gt
            bkg = tt < _EPS
            gamma = ts / jnp.where(bkg, 1.0, tt)
            x_new = x_c + alpha * p_c + gamma * s_c
            r_new = s_c - gamma * t_c
            rho_new = r_new @ g_r0
            rr_new = r_new @ (G @ r_new)
            beta = (rho_new / jnp.where(jnp.abs(rho) < _EPS, 1.0, rho)) * (
                alpha / jnp.where(jnp.abs(gamma) < _EPS, 1.0, gamma)
            )
            p_new = r_new + beta * (p_c - gamma * v_c)
            bk = jnp.logical_or(bka, bkg)
            step_ok = jnp.logical_and(active, jnp.logical_not(bk))
            # f32 coordinate-drift guard — same semantics as sstep_cg's:
            # a non-positive ⟨r,r⟩ quadratic form discards the step and
            # ends the cycle, never reports convergence.
            rr_bad = rr_new <= 0.0
            good = jnp.logical_and(step_ok, jnp.logical_not(rr_bad))
            x_c = jnp.where(good, x_new, x_c)
            r_c = jnp.where(good, r_new, r_c)
            p_c = jnp.where(good, p_new, p_c)
            rho = jnp.where(good, rho_new, rho)
            rr_c = jnp.where(good, rr_new, rr_c)
            # Residual curve at absolute iteration k + it (Gram quadratic
            # form — no extra collective).
            hist = jnp.where(
                active,
                hist.at[k + it].set(jnp.sqrt(jnp.maximum(rr_c, 0.0))),
                hist)
            # φ-best: ⟨b,x⟩ and ⟨x,r⟩ from the probe columns — no extra dots
            phi = -0.5 * (bx + g_b @ x_c) - 0.5 * (
                g_x0 @ r_c + x_c @ (G @ r_c)
            )
            improved = jnp.logical_and(good, phi < best_phi)
            best_xc = jnp.where(improved, x_c, best_xc)
            best_rc = jnp.where(improved, r_c, best_rc)
            best_phi = jnp.where(improved, phi, best_phi)
            best_imp = jnp.logical_or(best_imp, improved)
            it = it + active.astype(jnp.int32)
            brk_rec = jnp.logical_or(brk_rec, jnp.logical_and(active, bk))
            conv = jnp.logical_and(
                rr_c > 0.0, jnp.sqrt(jnp.maximum(rr_c, 0.0)) < tol * b_norm)
            ev = jnp.logical_and(good, conv)
            if j == 0:
                # Stagnation — same first-step drift-guard semantics as
                # sstep_cg: identical carries would repeat the failure.
                ev = jnp.logical_or(ev, jnp.logical_and(step_ok, rr_bad))
            term = jnp.logical_or(term, ev)
            stop = jnp.logical_or(
                stop,
                jnp.logical_or(jnp.logical_and(active, bk),
                               jnp.logical_or(
                                   ev, jnp.logical_and(step_ok, rr_bad))),
            )

        # ---- materialize the cycle ----------------------------------------
        # On basis breakdown the coords are still the one-hot inits, but the
        # overflowed basis may hold inf (0·inf = NaN in the combine) — keep
        # the carried vectors/scalars instead.
        out = be.block_combine(
            jnp.stack([x_c, r_c, p_c, nc_c, best_xc, best_rc]), V
        )
        x2 = be.where(
            brk_basis, x, be.axpy(1.0, be.block_col(out, 0), x))
        xb_v = be.axpy(1.0, be.block_col(out, 4), x)  # x_start + V·best_xc
        best2 = BestState(
            be.where(best_imp, xb_v, best.x),
            be.where(best_imp, be.block_col(out, 5), best.r),
            best_phi,
        )
        nc2 = NCState(
            jnp.logical_or(nc.found, cyc_found),
            be.where(cyc_imp, be.block_col(out, 3), nc.dir),
            jnp.where(cyc_imp, cyc_curv, nc.curv),
        )
        r2 = be.where(brk_basis, r, be.block_col(out, 1))
        p2 = be.where(brk_basis, p, be.block_col(out, 2))
        bx2 = jnp.where(brk_basis, bx, bx + g_b @ x_c)
        return (x2, r2, p2, bx2, rr_c, it, term, brk_basis, brk_rec,
                nc2, best2, hist, G)

    done0 = rn0 < tol * b_norm
    hist0 = jnp.full((max_iters,), jnp.nan, jnp.float32)
    nc0 = nc_init(be, b_)
    best0 = best_init(be, b_, x0_, r0)
    zero_i = jnp.zeros((), jnp.int32)
    no = jnp.zeros((), bool)

    if not spec.adaptive:
        # Overlap: monomial coeff arrays ⇒ depth-resolved prefix guard for
        # the speculative second cycle (see sstep_cg).
        cf_mono = None
        if overlap:
            cf_mono = (jnp.zeros((2 * s_run,), jnp.float32),
                       jnp.zeros((2 * s_run,), jnp.float32),
                       jnp.ones((2 * s_run,), jnp.float32))

        def cond(carry):
            return jnp.logical_and(carry[5] < max_iters,
                                   jnp.logical_not(carry[6]))

        def body(carry):
            (x, r, p, bx, rr, k, done, brk0, nc, best, syncs, bb,
             hist, nc_lam) = carry
            (x, r, p, bx, rr, it, term, brk_basis, brk_rec,
             nc, best, hist, G) = cycle(x, r, p, bx, k, nc, best, s_run,
                                        cf_mono, no, hist)
            # Recurrence (ρ/ω) collapse is a breakdown too: reporting it
            # keeps parity with solvers.bicgstab's breakdown flag, and
            # routing it through the fallback restarts the standard solver
            # with a FRESH r0* from the frozen iterate — the classic
            # Bi-CG-STAB restart remedy, which typically recovers where
            # the stale shadow residual cannot.
            brk = jnp.logical_or(brk_basis, brk_rec)
            # λ_min estimate from the monomial p-chain Gram — same free
            # extraction the adaptive path uses, 2s-deep chains here.
            L = 2 * s_run + 1
            ritz, rok = ritz_from_segment(G[:L, :L], _segment_shift(L))
            nc_lam = _lam_floor(nc_lam, ritz, rok, lam)
            return (x, r, p, bx, rr, k + it, jnp.logical_or(term, brk),
                    jnp.logical_or(brk0, brk), nc, best, syncs + 1,
                    jnp.logical_or(bb, brk_basis), hist, nc_lam)

        init = (x0_, r0, r0, bx0, rn0 * rn0, zero_i, done0, no, nc0,
                best0, zero_i, no, hist0, jnp.zeros((), jnp.float32))
        (x, r, _, _, _, k, _, brk, nc, best, syncs,
         basis_brk, hist, nc_lam) = jax.lax.while_loop(cond, body, init)
        degraded = no
    else:
        # ---- bootstrap: monomial cycles at the f32-safe chain depth -------
        # Bi-CG-STAB chains run 2s deep, so the budget is s_boot = 2
        # (4 power applications) — run ceil(s/s_boot)+1 cycles so the first
        # full-depth cycle launches from k > s iterations: its 4s+1 chain
        # vectors need a K_{2k+2s+1} that can hold them WITH margin (at
        # k = s exactly the space dimension equals the column count and
        # the joint basis is borderline rank-deficient — same structural
        # argument as sstep_cg, with two A-applications per iteration).
        # Real iterations + free Ritz estimates.
        s_boot = max(1, min(s_run, BOOT_APPLICATIONS // 2))
        n_boot = -(-s_run // s_boot) + 1
        # Monomial coefficient arrays ⇒ prefix-guarded bootstrap cycles
        # (see sstep_cg — near-converged warm starts run partial cycles).
        cf0 = (jnp.zeros((2 * s_boot,), jnp.float32),
               jnp.zeros((2 * s_boot,), jnp.float32),
               jnp.ones((2 * s_boot,), jnp.float32))
        xb, rb, pb, bxb = x0_, r0, r0, bx0
        rrb, kb, ncb, bestb = rn0 * rn0, zero_i, nc0, best0
        termb, brkb, brkb_basis, Gb = done0, no, no, None
        histb = hist0
        for _ in range(n_boot):
            # Mask breakdowns from cycles traced after the solve already
            # terminated (see sstep_cg's bootstrap note).
            live = jnp.logical_not(jnp.logical_or(termb, brkb))
            (xb, rb, pb, bxb, rrb, it_, term_, brk_basis_, brk_rec_,
             ncb, bestb, histb, Gb) = cycle(
                xb, rb, pb, bxb, kb, ncb, bestb, s_boot, cf0,
                jnp.logical_not(live), histb)
            kb = kb + it_
            termb = jnp.logical_or(termb, term_)
            brkb_basis = jnp.logical_or(
                brkb_basis, jnp.logical_and(brk_basis_, live))
            brkb = jnp.logical_or(
                brkb,
                jnp.logical_and(jnp.logical_or(brk_basis_, brk_rec_), live))
        Lb = 2 * s_boot + 1
        ritz, rok = ritz_from_segment(Gb[:Lb, :Lb], _segment_shift(Lb))
        al, bt, gm = spec.coeffs(ritz, rok, 2 * s_run)
        nc_lam0 = _lam_floor(jnp.zeros((), jnp.float32), ritz, rok, lam)

        def cond(carry):
            return jnp.logical_and(carry[5] < max_iters,
                                   jnp.logical_not(carry[6]))

        def body(carry):
            (x, r, p, bx, rr, k, done, brk0, nc, best, syncs,
             al, bt, gm, adaptive, degraded, bb, hist, nc_lam) = carry
            (xn, rn, pn, bxn, rrn, it, term, brk_basis, brk_rec,
             ncn, bestn, hist, G) = cycle(
                x, r, p, bx, k, nc, best, s_run, (al, bt, gm), no, hist)
            # Fallback chain: an adaptive-basis guard failure degrades to
            # the monomial basis (sticky, cycle discarded); monomial guard
            # failure and ρ/ω collapse are hard breakdowns for the
            # standard-solver fallback.
            degrade = jnp.logical_and(brk_basis, adaptive)
            hard_basis = jnp.logical_and(
                brk_basis, jnp.logical_not(adaptive))
            hard = jnp.logical_or(hard_basis, brk_rec)
            L = 2 * s_run + 1
            ritz, rok = ritz_from_segment(
                G[:L, :L], _segment_T((al, bt, gm), L))
            nc_lam = _lam_floor(nc_lam, ritz, rok, lam)
            fa, fb_, fg = spec.coeffs(ritz, rok, 2 * s_run)
            refresh = jnp.logical_and(
                adaptive,
                jnp.logical_and(rok, jnp.logical_not(brk_basis)))
            al2 = jnp.where(degrade, 0.0, jnp.where(refresh, fa, al))
            bt2 = jnp.where(degrade, 0.0, jnp.where(refresh, fb_, bt))
            gm2 = jnp.where(degrade, 1.0, jnp.where(refresh, fg, gm))
            return (xn, rn, pn, bxn, rrn, k + it,
                    jnp.logical_or(term, hard),
                    jnp.logical_or(brk0, hard), ncn, bestn, syncs + 1,
                    al2, bt2, gm2,
                    jnp.logical_and(adaptive, jnp.logical_not(degrade)),
                    jnp.logical_or(degraded, degrade),
                    jnp.logical_or(bb, hard_basis), hist, nc_lam)

        init = (xb, rb, pb, bxb, rrb, kb, jnp.logical_or(termb, brkb),
                brkb, ncb, bestb, jnp.asarray(n_boot, jnp.int32),
                al, bt, gm,
                jnp.logical_and(rok, jnp.logical_not(brkb)), no,
                brkb_basis, histb, nc_lam0)
        (x, r, _, _, _, k, _, brk, nc, best, syncs,
         _, _, _, _, degraded, basis_brk, hist, nc_lam) = jax.lax.while_loop(
            cond, body, init)

    res = KrylovResult(
        be.lower(x), be.lower(r), be.lower(best.x), be.lower(best.r),
        be.lower(nc.dir), nc.found, nc.curv, k, be.norm(r),
        syncs=syncs, breakdown=brk, basis_degraded=degraded,
        basis_breakdown=basis_brk, residual_history=hist,
        nc_lambda=jnp.minimum(nc_lam, nc.curv),
    )
    if not fallback:
        return res
    return _merge_fallback(
        res,
        lambda xs: bicgstab(A, b, xs, lam=lam, max_iters=max_iters, tol=tol,
                            backend=backend),
    )
