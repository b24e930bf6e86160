"""Device time of the Armijo line search per traced step, in ms: the
operations under the program's ``line_search`` scope (``bench/phases.py``)."""
from bench import phases


def read(ctx):
    ms = phases.per_step(ctx)
    return None if ms is None else ms["line_search"]
