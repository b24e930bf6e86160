"""A plain outer Hessian-free step: the reference the program is held to.

It imports nothing of the program. It follows the mathematics of
``repro.core.hf.hf_step`` as ``HFOptConfig``'s defaults configure it —
Bi-CG-STAB on the damped exact stochastic Hessian, pytree vectors, no
preconditioner, the negative-curvature candidate in its passive
("truncate") form, Armijo backtracking, Levenberg-Marquardt damping and the
non-finite-step sentinel — written once more in straightforward
``jax.numpy``. The Hessian-vector products are ``jax.linearize`` of
``jax.grad`` on the curvature rows (Pearlmutter's forward over reverse).

The warm start's jitter (a deterministic pseudo-noise of the gradient, the
element position and the step) is part of the algorithm's definition, so
its formula is written out here as well.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

EPS = 1e-20


class Settings(NamedTuple):
    max_cg_iters: int = 16
    cg_tol: float = 5e-3
    init_damping: float = 1.0
    damping_inc: float = 1.5
    damping_dec: float = 1.5
    cg_decay: float = 0.95
    ls_c: float = 1e-2
    ls_beta: float = 0.5
    max_backtracks: int = 12
    krylov_jitter: float = 1e-3
    nc_min_step: float = 0.1
    hvp_batch_frac: float = 0.25


def settings(optimizer: dict) -> Settings:
    """The reference's settings from a traffic file's optimizer entry,
    refusing any option the reference does not implement."""
    plain = {"name": "bicgstab", "krylov_backend": "tree",
             "curvature_mode": "linearize", "precondition": False,
             "sstep_s": 1, "overlap": False, "nc_mode": "truncate",
             "reject_nonfinite": True, "strict_descent": False}
    kw = {}
    for k, v in optimizer.items():
        if k in plain:
            if v != plain[k]:
                raise ValueError(f"the reference has no {k}={v!r}")
        elif k in Settings._fields:
            kw[k] = v
        else:
            raise ValueError(f"unknown optimizer setting {k!r}")
    return Settings(**kw)


# ---------------------------------------------------------- vector algebra --
def leaves(t):
    return jax.tree_util.tree_leaves(t)


def tmap(f, *ts):
    return jax.tree_util.tree_map(f, *ts)


def dot(a, b):
    return jnp.sum(jnp.stack([jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))
                              for x, y in zip(leaves(a), leaves(b))]))


def norm(a):
    return jnp.sqrt(dot(a, a))


def axpy(alpha, x, y):
    return tmap(lambda u, v: alpha * u + v, x, y)


def scale(alpha, x):
    return tmap(lambda u: alpha * u, x)


def where(c, a, b):
    return tmap(lambda u, v: jnp.where(c, u, v), a, b)


def pseudo_noise(tree, step):
    """sin of a value/position/step hash, leaf by leaf, in [-1, 1]."""
    out = []
    ls, treedef = jax.tree_util.tree_flatten(tree)
    sf = jnp.asarray(step, jnp.float32)
    for i, x in enumerate(ls):
        pos = jnp.zeros(x.shape, jnp.float32)
        for d in range(x.ndim):
            pos = pos + jax.lax.broadcasted_iota(jnp.float32, x.shape, d) * (
                0.7391 + 0.2113 * d)
        out.append(jnp.sin(x.astype(jnp.float32) * 1234.567
                           + pos * (1.0 + 0.13 * i) + sf * 0.61803
                           + 0.5 * (i + 1)))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------- the solve --
def bicgstab(A, b, x0, lam, st: Settings):
    """Bi-CG-STAB with the negative-curvature probe and best-model
    tracking. Returns (x_best, r_best, nc_dir, nc_found, nc_curv, iters)."""
    zeros = tmap(jnp.zeros_like, b)
    b_norm = norm(b)
    r0 = tmap(jnp.subtract, b, A(x0))
    r0s = r0

    def phi(x, r):
        return -0.5 * dot(b, x) - 0.5 * dot(x, r)

    def probe(d, dAd, d_sq, nc):
        found, ndir, curv = nc
        raw = (dAd - lam * d_sq) / jnp.maximum(d_sq, EPS)
        is_nc = raw < 0.0
        better = jnp.logical_and(is_nc, raw < curv)
        ndir = where(better, scale(1.0 / jnp.sqrt(jnp.maximum(d_sq, EPS)), d),
                     ndir)
        return (jnp.logical_or(found, is_nc), ndir,
                jnp.where(better, raw, curv))

    def cond(c):
        return jnp.logical_and(c["k"] < st.max_cg_iters,
                               jnp.logical_not(c["done"]))

    def body(c):
        x, r, p, rho = c["x"], c["r"], c["p"], c["rho"]
        v = A(p)
        nc = probe(p, dot(v, p), dot(p, p), c["nc"])
        den = dot(v, r0s)
        bad_a = jnp.abs(den) < EPS
        alpha = rho / jnp.where(bad_a, 1.0, den)
        s = axpy(-alpha, v, r)
        t = A(s)
        nc = probe(s, dot(t, s), dot(s, s), nc)
        st_dot, tt = dot(s, t), dot(t, t)
        bad_g = tt < EPS
        gamma = st_dot / jnp.where(bad_g, 1.0, tt)
        x_new = axpy(gamma, s, axpy(alpha, p, x))
        r_new = axpy(-gamma, t, s)
        rho_new, rr_new = dot(r_new, r0s), dot(r_new, r_new)
        beta = (rho_new / jnp.where(jnp.abs(rho) < EPS, 1.0, rho)) * (
            alpha / jnp.where(jnp.abs(gamma) < EPS, 1.0, gamma))
        p_new = axpy(-beta * gamma, v, axpy(beta, p, r_new))
        bad = jnp.logical_not(jnp.logical_and(jnp.isfinite(rho_new),
                                              jnp.isfinite(rr_new)))
        brk = jnp.logical_or(jnp.logical_or(bad_a, bad_g), bad)
        x, r, p = where(brk, x, x_new), where(brk, r, r_new), where(brk, p, p_new)
        rho = jnp.where(brk, rho, rho_new)
        ph = phi(x, r)
        better = jnp.logical_and(ph < c["phi"], jnp.logical_not(brk))
        done = jnp.logical_or(brk, jnp.sqrt(rr_new) < st.cg_tol * b_norm)
        return {"x": x, "r": r, "p": p, "rho": rho, "k": c["k"] + 1,
                "done": done, "nc": nc,
                "xb": where(better, x, c["xb"]), "rb": where(better, r, c["rb"]),
                "phi": jnp.where(better, ph, c["phi"])}

    init = {"x": x0, "r": r0, "p": r0, "rho": dot(r0, r0s),
            "k": jnp.zeros((), jnp.int32),
            "done": norm(r0) < st.cg_tol * b_norm,
            "nc": (jnp.zeros((), bool), zeros, jnp.zeros((), jnp.float32)),
            "xb": x0, "rb": r0, "phi": phi(x0, r0)}
    c = jax.lax.while_loop(cond, body, init)
    found, nc_dir, nc_curv = c["nc"]
    return c["xb"], c["rb"], nc_dir, found, nc_curv, c["k"]


def armijo(f, params, f0, delta, gd, st: Settings):
    """Backtracking from α = 1 by ``ls_beta`` until f(θ+αδ) ≤ f0 + c·α·gᵀδ;
    a failed search takes a zero step."""
    def trial(alpha):
        return f(tmap(lambda d, p: (alpha * d.astype(jnp.float32)
                                    + p.astype(jnp.float32)).astype(p.dtype),
                      delta, params))

    def cond(c):
        return jnp.logical_and(c[2] < st.max_backtracks, jnp.logical_not(c[3]))

    def body(c):
        alpha = c[0]
        fn = trial(alpha)
        ok = fn <= f0 + st.ls_c * alpha * gd
        return (jnp.where(ok, alpha, alpha * st.ls_beta), fn, c[2] + 1, ok)

    alpha, fn, k, ok = jax.lax.while_loop(
        cond, body, (jnp.asarray(1.0), f0, jnp.zeros((), jnp.int32),
                     jnp.zeros((), bool)))
    return jnp.where(ok, alpha, 0.0), jnp.where(ok, fn, f0), k


# ---------------------------------------------------------------- one step --
def init_state(params, st: Settings):
    return {"lam": jnp.asarray(st.init_damping, jnp.float32),
            "prev": tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "step": jnp.zeros((), jnp.int32)}


def hf_step(loss, params, state, batch, curv_batch, st: Settings):
    """One outer step of ``loss(params, batch)``. Returns (params, state,
    readings): the step's loss, its gradient's norm and per-leaf norms,
    the reduction its damped quadratic model predicts for the step taken,
    the solve's iterations and the line search's evaluations."""
    f0, g = jax.value_and_grad(loss)(params, batch)
    _, hvp = jax.linearize(lambda p: jax.grad(loss)(p, curv_batch), params)

    def A(v):
        hv = hvp(tmap(lambda t, p: t.astype(p.dtype), v, params))
        return tmap(lambda h, x: h.astype(jnp.float32) + lam * x, hv, v)

    lam = state["lam"]
    b = tmap(lambda x: -x.astype(jnp.float32), g)
    x0 = scale(st.cg_decay, state["prev"])
    noise = pseudo_noise(g, state["step"])
    jit_scale = st.krylov_jitter * jnp.maximum(norm(g), 1e-8) / jnp.maximum(
        norm(noise), 1e-20)
    x0 = axpy(jit_scale, noise, x0)
    xb, rb, nc_dir, nc_found, nc_curv, iters = bicgstab(A, b, x0, lam, st)

    # the solution, turned to descent, against the negative-curvature
    # direction at the solution's scale, under the damped quadratic model
    gx = dot(g, xb)
    sign = jnp.where(jnp.sign(gx) == 0, 1.0, -jnp.sign(gx))
    sol = scale(sign, xb)
    xAx = dot(xb, tmap(jnp.subtract, b, rb))
    m_sol = sign * gx + 0.5 * xAx
    nc_scale = jnp.maximum(norm(sol), st.nc_min_step)
    nc = scale(nc_scale, nc_dir)
    gd_nc = dot(g, nc)
    s_nc = jnp.where(-jnp.sign(gd_nc) == 0, 1.0, -jnp.sign(gd_nc))
    nc = scale(s_nc, nc)
    g_nc = dot(g, nc)
    m_nc = jnp.where(nc_found, g_nc + 0.5 * (nc_curv + lam) * nc_scale ** 2,
                     jnp.inf)
    take_nc = m_nc < m_sol
    delta = where(take_nc, nc, sol)
    m_lin = jnp.where(take_nc, g_nc, sign * gx)
    m_quad = jnp.where(take_nc, m_nc - g_nc, 0.5 * xAx)
    degenerate = norm(delta) < 1e-12
    delta = where(degenerate, b, delta)
    m_lin = jnp.where(degenerate, -dot(g, g), m_lin)
    m_quad = jnp.where(degenerate, 0.0, m_quad)

    alpha, f_new, ls_evals = armijo(lambda p: loss(p, batch), params, f0,
                                    delta, dot(g, delta), st)

    # Levenberg-Marquardt damping on the reduction the model predicted
    pred = jnp.minimum(alpha * m_lin + alpha ** 2 * m_quad, -1e-20)
    actual = f_new - f0
    rho = actual / jnp.minimum(pred, -1e-20)
    lam_new = jnp.where(rho < 0.25, lam * st.damping_inc,
                        jnp.where(rho > 0.75, lam / st.damping_dec, lam))
    lam_new = jnp.where(actual > 0.0, lam * st.damping_inc ** 2, lam_new)
    lam_new = jnp.clip(lam_new, 1e-8, 1e8)
    new_params = tmap(lambda d, p: (alpha * d.astype(jnp.float32)
                                    + p.astype(jnp.float32)).astype(p.dtype),
                      delta, params)
    taken = scale(alpha, delta)

    # a non-finite step is rejected: parameters kept, warm start dropped
    accept = jnp.logical_and(jnp.isfinite(f_new), jnp.isfinite(norm(taken)))
    lam_new = jnp.where(accept, lam_new,
                        jnp.clip(lam * st.damping_inc ** 2, 1e-8, 1e8))
    new_params = where(accept, new_params, params)
    taken = where(accept, taken, tmap(jnp.zeros_like, state["prev"]))
    readings = {"loss": f0, "loss_new": f_new, "grad_norm": norm(g),
                "pred": alpha * m_lin + alpha ** 2 * m_quad,
                "grad_leaf_norms": jnp.stack([norm(x) for x in leaves(g)]),
                "cg_iters": iters, "ls_evals": ls_evals}
    return new_params, {"lam": lam_new, "prev": taken,
                        "step": state["step"] + 1}, readings


def model_value(loss, params, batch, curv_batch, lam, delta):
    """The damped quadratic model's change for the step ``delta`` from
    ``params``: gᵀδ + ½ δᵀ(H + λI)δ, with the full-batch gradient and the
    Hessian on the curvature rows, as ``hf_step`` defines them."""
    g = jax.grad(loss)(params, batch)
    _, hd = jax.jvp(lambda p: jax.grad(loss)(p, curv_batch), (params,),
                    (tmap(lambda d, p: d.astype(p.dtype), delta, params),))
    return dot(g, delta) + 0.5 * (dot(delta, hd) + lam * dot(delta, delta))
