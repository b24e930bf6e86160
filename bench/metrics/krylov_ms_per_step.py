"""Device time of the Krylov solve per traced step, in ms: the operations
under the program's ``krylov_solve`` scope, its curvature products included
(``bench/phases.py``)."""
from bench import phases


def read(ctx):
    ms = phases.per_step(ctx)
    return None if ms is None else ms["krylov_solve"]
