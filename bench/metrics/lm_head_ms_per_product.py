"""Device time of the tied output head and the masked cross-entropy in one
curvature-operator application, in ms: the operations under both the
model's ``lm_head`` scope and the step's ``curvature_product`` phase, over
the traced solves' applications (``bench/scoped_product_ms.py``)."""
from bench import scoped_product_ms


def read(ctx):
    return scoped_product_ms.ms_per_product(ctx, "lm_head")
