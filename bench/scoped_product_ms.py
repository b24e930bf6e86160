"""Device time of one model scope inside the curvature products, per
operator application, from a profiler trace.

The model runs a part of itself inside ``jax.named_scope(scope)`` (the
attention sublayers, the tied head), and the HF step runs each application
of the curvature operator inside the phase ``curvature_product``; XLA keeps
both in each instruction's ``op_name``. Read with ``bench/phases.py``
(``read_ops``, ``paths``, ``own_ns``): each instant of the step module's
device time in the traced window goes to its innermost operation, and
counts here where that operation carries both names. The sum is divided by
the traced solves' operator applications (``bench/flops.py::
bicgstab_products`` of each step's own ``cg_iters``), because the number of
applications follows the seed's work while the time of one stays put.
"""
from __future__ import annotations

from bench import flops, phases

PRODUCT = "curvature_product"


def scoped_ns(ops, lo: int, hi: int, scope: str) -> int:
    """Device time in [lo, hi) of the step module's operations whose scopes
    hold both ``curvature_product`` and ``scope``."""
    ops = [o for o in ops if o.module == phases.STEP_MODULE]
    names = (PRODUCT, scope)
    ivs = [(o.start, o.end, set(names) <= set(p))
           for o, p in zip(ops, phases.paths(ops, names))]
    return phases.own_ns(ivs, lo, hi).get(True, 0)


def carries(ops, scope: str) -> bool:
    return any(scope in phases.IDENT.findall(o.op_name or "") for o in ops
               if o.module == phases.STEP_MODULE)


def ms_per_product(ctx, scope: str):
    """The mean over the cell's devices, in ms per operator application.
    Nothing for a trace without TPU operations (a run on the CPU), and for
    a program whose step names no ``scope``."""
    if not any(ctx["trace"].device_ops.get(d) for d in ctx["devices"]):
        return None
    path = phases.find_xplane(ctx["trace"], ctx["devices"][0])
    with open(path, "rb") as f:
        ops = phases.read_ops(f.read())
    per_dev = [ops.get(d, []) for d in ctx["devices"]]
    if not any(carries(o, scope) for o in per_dev):
        return None
    ns = sum(scoped_ns(o, ctx["lo"], ctx["hi"], scope) for o in per_dev)
    products = sum(flops.bicgstab_products(m["cg_iters"])
                   for m in ctx["traced_steps"])
    return ns / len(per_dev) / products * 1e-6
