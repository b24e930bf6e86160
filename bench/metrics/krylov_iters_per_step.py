"""Krylov iterations per outer step: the mean of the program's own
``cg_iters`` counter over the window's steps (``core/solvers.py``)."""
import statistics


def read(ctx):
    return statistics.fmean(m["cg_iters"] for m in ctx["steps"])
