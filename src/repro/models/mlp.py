"""The paper's own networks: fully-connected classifiers (MNIST 784-400-10,
TIMIT 360-512x3-1973, and the Fig. 4 network 784-400-150-10).

Exposes the same (loss_fn, logits_fn, out_loss_fn) split the HF optimizer
needs for its Gauss-Newton variants.

On the TPU, a tanh network with float32 parameters and inputs takes its
exact curvature product from one Pallas pass (``kernels/mlp_hvp.py``):
``loss_fn`` is then ``_fused_loss``, a ``jax.custom_jvp`` whose tangent is
``Σ⟨t, g⟩`` with ``g`` from ``mlp_value_and_grad``, itself a
``jax.custom_jvp`` (plain AD in the primal; as its tangent, a hand-written
backprop that keeps the kernel's residuals, and the kernel). So
``jax.linearize(jax.grad(loss_fn))``, which the curvature engine builds in
every mode, lands on the kernel, while the plain loss (the line search) and
``jax.grad`` (the gradient) stay plain jnp.
Everything else (relu, sigmoid, other dtypes, the CPU) keeps the plain
path. ``custom_vjp`` and ``linear_call`` cannot be differentiated at
second order (kernels/flash_ad.py), so neither appears here.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from ..kernels import ops as kops


class MLPApi(NamedTuple):
    init: callable
    loss_fn: callable
    logits_fn: callable
    out_loss_fn: callable
    accuracy: callable


def build_mlp(layer_dims: Sequence[int], activation: str = "tanh") -> MLPApi:
    """layer_dims = (in, hidden..., n_classes). Batch: {"x": (B,D), "y": (B,) int}."""
    act = {"tanh": jnp.tanh, "relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid}[activation]

    def init(key):
        params = []
        keys = jax.random.split(key, len(layer_dims) - 1)
        for k, din, dout in zip(keys, layer_dims[:-1], layer_dims[1:]):
            params.append({
                "w": jax.random.normal(k, (din, dout)) * jnp.sqrt(1.0 / din),
                "b": jnp.zeros((dout,)),
            })
        return params

    def logits_fn(params, batch):
        h = batch["x"]
        for layer in params[:-1]:
            h = act(h @ layer["w"] + layer["b"])
        return h @ params[-1]["w"] + params[-1]["b"]

    def out_loss_fn(logits, batch):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
        return jnp.mean(nll)

    def loss_fn(params, batch):
        if activation == "tanh" and _fused_hvp_route(params, batch["x"]):
            return _fused_loss(params, batch["x"], batch["y"])
        return out_loss_fn(logits_fn(params, batch), batch)

    def accuracy(params, batch):
        pred = jnp.argmax(logits_fn(params, batch), axis=-1)
        return jnp.mean((pred == batch["y"]).astype(jnp.float32))

    return MLPApi(init, loss_fn, logits_fn, out_loss_fn, accuracy)


# ---------------------------------------------------------------------------
# The fused exact curvature product of a tanh MLP
# ---------------------------------------------------------------------------


def _on_chip() -> bool:
    """Where the kernels compile for the chip (``kernels/ops.py``)."""
    return not kops._default_interpret()


def _fused_hvp_route(params, x) -> bool:
    return _on_chip() and all(
        t.dtype == jnp.float32 for t in jax.tree_util.tree_leaves((params, x)))


def _mean_nll(logp, y):
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0])


def _plain_loss(params, x, y):
    h = x
    for layer in params[:-1]:
        h = jnp.tanh(h @ layer["w"] + layer["b"])
    z = h @ params[-1]["w"] + params[-1]["b"]
    return _mean_nll(jax.nn.log_softmax(z, axis=-1), y)


def _forward_backward(params, x, y):
    """Hand-written backprop of the tanh MLP's mean cross-entropy:
    (loss, grads, residuals). The residuals are what the curvature product
    reads per row: the layers' inputs ``x, a_1 … a_{L-1}``, the gradients
    ``e_l = ∂loss/∂a_l`` and the softmax ``p``."""
    acts = [x]
    for layer in params[:-1]:
        acts.append(jnp.tanh(acts[-1] @ layer["w"] + layer["b"]))
    z = acts[-1] @ params[-1]["w"] + params[-1]["b"]
    logp = jax.nn.log_softmax(z, axis=-1)
    loss = _mean_nll(logp, y)
    p = jnp.exp(logp)
    delta = (p - jax.nn.one_hot(y, z.shape[-1], dtype=p.dtype)) / x.shape[0]
    grads, errs = [None] * len(params), [None] * (len(params) - 1)
    for l in reversed(range(len(params))):
        grads[l] = {"w": acts[l].T @ delta, "b": jnp.sum(delta, axis=0)}
        if l:
            errs[l - 1] = delta @ params[l]["w"].T
            delta = (1.0 - acts[l] * acts[l]) * errs[l - 1]
    return loss, grads, (acts, errs, p)


def _zero_tangent(t) -> bool:
    return isinstance(t, SymbolicZero)


def _instantiate(tangents, primals):
    return jax.tree_util.tree_map(
        lambda t, p: jnp.zeros_like(p) if _zero_tangent(t) else t,
        tangents, primals, is_leaf=_zero_tangent)


def _dot(grads, tangents):
    """Σ⟨g, t⟩ over the leaves whose tangent is not a symbolic zero."""
    g, t = jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(
        tangents, is_leaf=_zero_tangent)
    return sum((jnp.vdot(a, b) for a, b in zip(g, t) if not _zero_tangent(b)),
               jnp.zeros((), jnp.float32))


@jax.custom_jvp
def mlp_value_and_grad(params, x, y):
    """(loss, ∇loss) of the tanh MLP by plain AD, as the gradient was before
    the kernel; its tangent, the exact Hessian-vector product, is the
    ``mlp_hvp`` kernel on the residuals of the hand-written backprop."""
    return jax.value_and_grad(_plain_loss)(params, x, y)


@functools.partial(mlp_value_and_grad.defjvp, symbolic_zeros=True)
def _mlp_value_and_grad_jvp(primals, tangents):
    params, x, y = primals
    dparams, dx, _ = tangents
    if not _zero_tangent(dx):             # a direction in the data: plain AD
        vg = jax.value_and_grad(_plain_loss)
        return jax.jvp(lambda p, xx: vg(p, xx, y), (params, x),
                       (_instantiate(dparams, params), dx))
    loss, grads, (acts, errs, p) = _forward_backward(params, x, y)
    dparams = _instantiate(dparams, params)
    gw, gb = kops.mlp_hvp(acts, errs, p, y, [l["w"] for l in params],
                          [t["w"] for t in dparams], [t["b"] for t in dparams])
    dgrads = [{"w": w, "b": b} for w, b in zip(gw, gb)]
    return (loss, grads), (_dot(grads, dparams), dgrads)


@jax.custom_jvp
def _fused_loss(params, x, y):
    return _plain_loss(params, x, y)


@functools.partial(_fused_loss.defjvp, symbolic_zeros=True)
def _fused_loss_jvp(primals, tangents):
    params, x, y = primals
    dparams, dx, _ = tangents
    if not _zero_tangent(dx):
        return jax.jvp(lambda p, xx: _plain_loss(p, xx, y), (params, x),
                       (_instantiate(dparams, params), dx))
    loss, grads = mlp_value_and_grad(params, x, y)
    return loss, _dot(grads, dparams)
