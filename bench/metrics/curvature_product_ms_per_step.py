"""Device time of the curvature-operator applications per traced step, in
ms: the operations under the program's ``curvature_product`` scope, nested
in ``krylov_solve`` (``bench/phases.py``)."""
from bench import phases


def read(ctx):
    ms = phases.per_step(ctx)
    return None if ms is None else ms["curvature_product"]
