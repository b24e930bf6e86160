"""How ``correct`` is decided for a training cell.

Set-up drives the program's compiled step through its first
``traffic["warm_steps"]`` steps on the window's own feed, and keeps, on the
host, what the comparison needs (``harness.Warm``). Afterwards the plain
reference (``bench/hf_reference.py`` over the configuration's
``reference_loss``, float32, matmuls at the ``highest`` precision) is run
twice over:

* free, from the seed's weights over the first ``FREE_STEPS`` steps, on
  the same batches: the steps a user's run begins with, at the initial
  damping, where the solve converges in a few iterations;
* one step at a time over the last ``STEADY_STEPS`` steps of set-up, each
  started from the program's own parameters and HF state (damping, warm
  start, step count) before that step, on its batch. By then the damping
  has fallen and each solve runs to its iteration cap, with the
  negative-curvature candidate and best-model tracking in play, as in the
  window. Starting each step from the program's state keeps a rounding-level
  branch of one step from compounding into the next.

The numbers, each the worst over the steps it covers:

* ``loss_gap`` — the relative gap between the program's and the
  reference's loss at the start of each compared step;
* ``grad_norm_gap`` — the relative gap between the first gradient's norms,
  as the program's step reports it (``grad_norm``);
* ``update_gap`` — the worst leaf's gap between the norms of the
  parameters' change over the free steps, against the larger of that leaf's
  reference norm and the median leaf's. Leaves whose reference gradient is
  under a thousandth of the median leaf's move by round-off alone and are
  left out;
* ``step_grad_norm_gap`` — ``grad_norm_gap`` at the steady steps;
* ``step_update_gap`` — ``update_gap`` of each steady step's own change;
* ``step_loss_new_gap`` — the relative gap between the loss after each
  steady step, on the program's and the reference's side;
* ``model_gap`` (``bench/calibrate.py`` only) — the relative gap between
  the reduction the program's damped quadratic model predicted for each
  steady step it took and the reference's model of that same step.

``bench/limits/<cell>.json`` names the numbers that are compared, each with
its limit and the readings it was set from. The four steady-step numbers
are read but not compared: at the program's own precision a sound step and
the control read alike there, so no limit lies between them (``PERF.md``,
section 2).
"""
from __future__ import annotations

import numpy as np

from bench import hf_reference

# Gradients under this share of the median leaf's count as nought.
NOUGHT = 1e-3
# The free steps from the seed that the reference follows.
FREE_STEPS = 3
# The last steps of set-up that are compared one at a time.
STEADY_STEPS = 2


def runner(jax, job, cell, dtype, precision):
    """One reference step, ``(params, state, batch) -> (params, state,
    readings)``, on the first device, ``state`` in the reference's form."""
    st = hf_reference.settings(cell.traffic["optimizer"])
    dev = jax.devices()[0]

    def loss(p, b):
        return job.reference_loss(p, b, dtype)

    step = jax.jit(lambda p, s, b, cb: hf_reference.hf_step(loss, p, s, b, cb, st))

    def run(params, state, batch):
        b = jax.device_put(batch, dev)
        with jax.default_matmul_precision(precision):
            return step(jax.device_put(params, dev), jax.device_put(state, dev),
                        b, job.curv_rows(b))

    return run, st


def ref_state(state):
    """The program's HF state in the reference's form."""
    return {"lam": state.lam, "prev": state.prev_delta, "step": state.step}


def side(jax, run, st, params0, warm, batches):
    """What one side (the reference, or the control in the program's
    place) reads, in the form ``program_side`` gives the program's."""
    params = jax.device_put(params0)
    state, free = hf_reference.init_state(params, st), []
    for b in batches[:FREE_STEPS]:
        params, state, r = run(params, state, b)
        free.append(jax.device_get(r))
    steady = []
    for snap, b in zip(warm.steady, batches[FREE_STEPS:]):
        p, _, r = run(snap["params"], ref_state(snap["state"]), b)
        steady.append(dict(jax.device_get(r), params=jax.device_get(p)))
    return {"free": free, "free_params": jax.device_get(params),
            "steady": steady}


def program_side(warm):
    """The program's readings. Its step reports ρ, the actual over the
    predicted reduction, so the prediction is the actual over ρ."""
    steady = []
    for s in warm.steady:
        m = s["metrics"]
        actual = m["loss_new"] - m["loss"]
        steady.append(dict(m, params=s["after"],
                           pred=actual / m["rho"] if actual else 0.0))
    return {"free": warm.metrics[:FREE_STEPS], "free_params": warm.free_params,
            "steady": steady}


def model_values(jax, job, warm, batches, got):
    """The reference's damped quadratic model (float32, ``highest``) of the
    step each steady step of ``got`` took, from the program's parameters
    and damping before it."""
    import jax.numpy as jnp

    def loss(p, b):
        return job.reference_loss(p, b, jnp.float32)

    q = jax.jit(lambda p, b, cb, lam, d: hf_reference.model_value(
        loss, p, b, cb, lam, d))
    out = []
    with jax.default_matmul_precision("highest"):
        for snap, b, a in zip(warm.steady, batches[FREE_STEPS:], got["steady"]):
            delta = jax.tree_util.tree_map(
                lambda x, y: np.asarray(x, np.float32) - np.asarray(y, np.float32),
                a["params"], snap["params"])
            b = jax.device_put(b)
            out.append(float(q(snap["params"], b, job.curv_rows(b),
                               snap["state"].lam, delta)))
    return out


def _leaves(jax, tree):
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def _rel(a, b):
    return abs(float(a) - float(b)) / abs(float(b))


def leaf_gap(jax, after, ref_after, before, ref_grad_leaf_norms):
    """The worst leaf's gap between the norms of the change from ``before``
    to ``after`` and to ``ref_after``, against the larger of the leaf's
    reference norm and the median leaf's."""
    p0 = _leaves(jax, before)
    d = np.array([np.linalg.norm(a - b) for a, b in zip(_leaves(jax, after), p0)])
    d_ref = np.array([np.linalg.norm(a - b)
                      for a, b in zip(_leaves(jax, ref_after), p0)])
    g = np.asarray(ref_grad_leaf_norms, np.float64)
    keep = g >= NOUGHT * np.median(g)
    floor = np.median(d_ref[keep])
    return float(np.max(np.abs(d - d_ref)[keep] / np.maximum(d_ref[keep], floor)))


def compare(jax, got, ref, params0, warm, model=None):
    """The numbers of the module docstring for one side ``got`` against the
    reference ``ref``, both in ``side``'s form; ``model``, where given,
    holds the reference's model values of ``got``'s steady steps
    (``model_values``)."""
    free, rfree = got["free"], ref["free"]
    steady, rsteady = got["steady"], ref["steady"]
    numbers = {
        "loss_gap": max(_rel(a["loss"], b["loss"]) for a, b in
                        zip(free + steady, rfree + rsteady)),
        "grad_norm_gap": _rel(free[0]["grad_norm"], rfree[0]["grad_norm"]),
        "update_gap": leaf_gap(jax, got["free_params"], ref["free_params"],
                               params0, rfree[0]["grad_leaf_norms"]),
        "step_grad_norm_gap": max(_rel(a["grad_norm"], b["grad_norm"])
                                  for a, b in zip(steady, rsteady)),
        "step_update_gap": max(
            leaf_gap(jax, a["params"], b["params"], snap["params"],
                     b["grad_leaf_norms"])
            for a, b, snap in zip(steady, rsteady, warm.steady)),
        "step_loss_new_gap": max(_rel(a["loss_new"], b["loss_new"])
                                 for a, b in zip(steady, rsteady)),
    }
    if model is not None:
        numbers["model_gap"] = max(abs(float(a["pred"]) - q) / max(abs(q), 1e-30)
                                   for a, q in zip(steady, model))
    return numbers


def reference(jax, job, cell, params0, warm, batches, dtype=None,
              precision="highest"):
    import jax.numpy as jnp

    run, st = runner(jax, job, cell, jnp.float32 if dtype is None else dtype,
                     precision)
    return side(jax, run, st, params0, warm, batches)


def training(jax, job, cell, k_params, warm, batches):
    """``{name: {"value", "limit"}}`` for the program's set-up steps, each
    number that the cell's limits file holds."""
    params0 = jax.device_get(job.make_params(k_params))
    ref = reference(jax, job, cell, params0, warm, batches)
    numbers = compare(jax, program_side(warm), ref, params0, warm)
    limits = cell.limits["limits"]
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
