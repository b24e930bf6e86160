"""Faults planted under the timed path, each a wrapper of a cell's job.

``correct`` has to come out false for every fault a one-chip training cell
can have; the benchmark's own runs plant none. ``bench/tests`` drives whole runs with
each fault on the CPU, and ``bench/calibrate.py`` reads what each fault
does to the compared numbers on the chip.
"""
from __future__ import annotations

import jax


def unchanged_state(job):
    """The step computes, and hands back the parameters and state it got."""
    step = job.opt.step

    def faulty(params, state, batch):
        _, _, metrics = step(params, state, batch)
        return params, state, metrics

    return job._replace(opt=job.opt._replace(step=faulty))


def half_batch(job):
    """The step sees the first half of its batch; its means are over it."""
    step = job.opt.step

    def faulty(params, state, batch):
        half = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        return step(params, state, half)

    return job._replace(opt=job.opt._replace(step=faulty))


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch}
