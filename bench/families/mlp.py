"""The program side of an MLP classifier cell (the paper's own networks).

Builds, through the program's public entry points only, what the harness
drives: ``repro.models.build_mlp`` for the network and
``repro.optim.make_optimizer`` with ``repro.configs.HFOptConfig`` for the
HF step, on one chip.

The data (``generate.timit_corpus``) lives on the device, and each step's
batch is drawn from it there.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench import generate


class Job(NamedTuple):
    loss_fn: Callable            # the program's loss (timed path, held-out eval)
    opt: Any                     # the program's optimizer (init, step)
    make_data: Callable          # key -> on-device data (one jitted call)
    make_params: Callable        # key -> initial parameters (one jitted call)
    draw: Callable               # (data, step) -> the step's batch
    heldout_loss: Optional[Callable]   # (params, data) -> held-out loss
    curv_rows: Callable          # global batch -> the curvature rows it takes
    matmuls: Callable            # rows -> the forward's (flops, n_dep) list
    reference_loss: Callable     # (params, batch, dtype) -> plain loss


def build(cfg, traffic, cfg_module, chips: int) -> Job:
    from repro.configs import HFOptConfig
    from repro.models import build_mlp
    from repro.optim import make_optimizer

    if traffic["generator"] != "timit_frames":
        raise ValueError(f"an MLP cell takes timit_frames traffic, not "
                         f"{traffic['generator']!r}")
    dims = cfg["layer_dims"]
    model = build_mlp(dims, cfg["activation"])
    if chips != 1:
        raise ValueError(f"an MLP cell runs on one chip, not {chips}")
    opt = make_optimizer(HFOptConfig(**traffic["optimizer"]), model.loss_fn,
                         model_out_fn=model.logits_fn,
                         out_loss_fn=model.out_loss_fn)
    B = traffic["batch"]
    frac = traffic["optimizer"].get("hvp_batch_frac", 0.25)

    def make_data(key):
        return jax.jit(lambda k: generate.timit_corpus(
            k, traffic, dims[0], dims[-1]))(key)

    @jax.jit
    def draw(data, step):
        return generate.timit_batch(data["train"], step, B,
                                    traffic["train_frames"])

    def make_params(key):
        return jax.jit(lambda k: cfg_module.init_params(k, cfg))(key)

    @jax.jit
    def heldout_loss(params, data):
        return model.loss_fn(params, data["heldout"])

    def curv_rows(batch):
        n = max(int(B * frac), 1)
        return jax.tree_util.tree_map(lambda x: x[:n], batch)

    def reference_loss(params, batch, dtype=jnp.float32):
        return cfg_module.reference_loss(params, batch, cfg, dtype)

    return Job(model.loss_fn, opt, make_data, make_params, draw, heldout_loss,
               curv_rows, lambda rows: cfg_module.matmuls(cfg, rows),
               reference_loss)
