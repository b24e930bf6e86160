"""The TIMIT Fig. 5 network, written plainly: weights from the seed, the
reference loss, and the matmul FLOPs of each pass.

The reference imports nothing of the program. It is the tanh MLP of the
paper's Fig. 5 (360-512-512-512-1973) with a softmax cross-entropy: the same
mathematics as ``repro.models.build_mlp``, in straightforward ``jax.numpy``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_params(key, cfg):
    """[{"w": (din, dout), "b": (dout,)}, ...]: N(0, 1/din) weights, zero
    biases, float32 — the pytree structure the program's MLP takes."""
    dims = cfg["layer_dims"]
    keys = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (din, dout), jnp.float32)
                  * jnp.sqrt(1.0 / din),
             "b": jnp.zeros((dout,), jnp.float32)}
            for k, din, dout in zip(keys, dims[:-1], dims[1:])]


def reference_loss(params, batch, cfg, dtype=jnp.float32):
    """Mean softmax cross-entropy of the tanh MLP, computed in ``dtype``."""
    act = {"tanh": jnp.tanh}[cfg["activation"]]
    h = batch["x"].astype(dtype)
    for i, layer in enumerate(params):
        h = h @ layer["w"].astype(dtype) + layer["b"].astype(dtype)
        if i < len(params) - 1:
            h = act(h)
    logp = jax.nn.log_softmax(h, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def matmuls(cfg, rows):
    """The forward pass's matrix products for ``rows`` examples, as
    (flops, n_dep): n_dep counts the operands that depend on the parameters
    (1 for the first layer, whose input is data; 2 for the others, whose
    input is an activation). 2·m·k·n FLOPs each."""
    dims = cfg["layer_dims"]
    return [(2.0 * rows * din * dout, 1 if i == 0 else 2)
            for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:]))]
