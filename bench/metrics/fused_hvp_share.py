"""The share of the traced steps' curvature products that ran as the fused
``mlp_hvp`` kernel, in percent: the launches of that ``pallas_call`` on the
device inside the traced window, over the operator applications of the
traced steps' Bi-CG-STAB solves (``bench/flops.py::bicgstab_products`` of
each step's own ``cg_iters``).

A launch is an operation of the step module whose ``op_name`` names the
kernel's scope (``pallas_call`` runs inside ``jax.named_scope(name)``); the
names come from the trace as ``bench/phases.py`` reads them. Nothing for a
program without the kernel, and for a trace without TPU operations."""
import importlib.util

from bench import flops, phases

KERNEL = "mlp_hvp"


def program_has_kernel() -> bool:
    return importlib.util.find_spec(f"repro.kernels.{KERNEL}") is not None


def launches(ops, lo: int, hi: int) -> int:
    """Operations of the step module that start in [lo, hi) and carry the
    kernel's scope in their ``op_name``."""
    return sum(1 for o in ops
               if o.module == phases.STEP_MODULE and lo <= o.start < hi
               and KERNEL in phases.IDENT.findall(o.op_name or ""))


def share(n_launches: int, steps) -> float:
    products = sum(flops.bicgstab_products(m["cg_iters"]) for m in steps)
    return 100.0 * n_launches / products


def read(ctx):
    if not program_has_kernel() or not any(
            ctx["trace"].device_ops.get(d) for d in ctx["devices"]):
        return None
    path = phases.find_xplane(ctx["trace"], ctx["devices"][0])
    with open(path, "rb") as f:
        ops = phases.read_ops(f.read())
    n = sum(launches(ops.get(d, []), ctx["lo"], ctx["hi"])
            for d in ctx["devices"])
    return share(n / len(ctx["devices"]), ctx["traced_steps"])
