"""Device time of the full-batch gradient per traced step, in ms: the
operations under the program's ``grad_build`` scope (``bench/phases.py``)."""
from bench import phases


def read(ctx):
    ms = phases.per_step(ctx)
    return None if ms is None else ms["grad_build"]
