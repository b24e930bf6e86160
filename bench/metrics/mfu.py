"""The whole HF step's share of the chips' peak, in percent: the matmul
FLOPs the traced steps require (``bench/flops.py`` over the
configuration's shapes, with each step's own ``cg_iters`` and
``ls_evals``) over the traced window's length times chips times the peak
of ``bench/peaks.json``."""
from bench import flops


def read(ctx):
    job, traffic = ctx["job"], ctx["traffic"]
    B = traffic["batch"]
    b = max(int(B * traffic["optimizer"].get("hvp_batch_frac", 0.25)), 1)
    full, curv = job.matmuls(B), job.matmuls(b)
    total = sum(flops.hf_step(full, curv, m["cg_iters"], m["ls_evals"])
                for m in ctx["traced_steps"])
    seconds = (ctx["hi"] - ctx["lo"]) * 1e-9
    return 100.0 * total / (seconds * ctx["chips"] * ctx["peak"]["flops_per_s"])
