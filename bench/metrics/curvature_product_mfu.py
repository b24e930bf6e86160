"""The curvature products' share of the chips' peak, in percent: the FLOPs
of the Bi-CG-STAB solve's products in the traced steps (``bench/flops.py``,
counted as ``mfu`` counts them, from each step's own ``cg_iters``) over the
device time of the operations under the program's ``curvature_product``
scope (``bench/phases.py``) times chips times the peak of
``bench/peaks.json``."""
from bench import flops, phases


def read(ctx):
    ms = phases.per_step(ctx)
    if ms is None:
        return None
    traffic, steps = ctx["traffic"], ctx["traced_steps"]
    b = max(int(traffic["batch"]
                * traffic["optimizer"].get("hvp_batch_frac", 0.25)), 1)
    total = (sum(flops.bicgstab_products(m["cg_iters"]) for m in steps)
             * flops.hvp(ctx["job"].matmuls(b)))
    seconds = ms["curvature_product"] * len(steps) * 1e-3
    return 100.0 * total / (seconds * ctx["chips"]
                            * ctx["peak"]["flops_per_s"])
