"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 1-12 \\
      [--control-seeds 1-3] [--witness-seeds 1-3] [--fault-seeds 1-3]

In one process, for each seed: the program's set-up steps against the
plain reference (the lower readings); for each control seed: the reference
computed in bfloat16, put in the program's place (its readings must fail a
limit); for each witness seed: the reference in float32 at JAX's default
matmul precision, the program's own, put in the program's place (what
rounding alone reads); for each fault seed: the program with each fault of
``bench/faults.py`` planted under it. Every number of ``bench/check.py``
is read, whether or not the cell's limits compare it. One JSON line
per reading on standard output, then a summary: the largest program
(and witness) reading and the smallest control and fault readings of
each number.
Limits go to ``bench/limits/<cell>.json`` by hand, with these readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import check, faults, harness  # noqa: E402


def seeds(text):
    out = []
    for part in text.split(","):
        if "-" in part:
            lo, hi = part.split("-")
            out.extend(range(int(lo), int(hi) + 1))
        elif part:
            out.append(int(part))
    return out


def program_reading(jax, job, cell, seed):
    """The program's set-up steps from ``seed`` against the reference:
    (numbers, what the control needs, diagnostics)."""
    k_params, drv, warm, _ = harness.start(jax, job, cell, seed)
    batches = harness.compared_batches(jax, drv, warm)
    del drv
    params0 = jax.device_get(job.make_params(k_params))
    ref = check.reference(jax, job, cell, params0, warm, batches)
    got = check.program_side(warm)
    numbers = check.compare(jax, got, ref, params0, warm,
                            check.model_values(jax, job, warm, batches, got))
    diag = {"cg_iters": [m["cg_iters"] for m in warm.metrics],
            "lam": [float(s["state"].lam) for s in warm.steady],
            "ref_cg_iters": [int(r["cg_iters"])
                             for r in ref["free"] + ref["steady"]]}
    return numbers, (params0, warm, batches, ref), diag


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--witness-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    cell = harness.Cell(args.workload)
    peaks = harness.load_json(os.path.join(harness.BENCH, "peaks.json"))
    harness.device_record(jax, cell.chips, peaks)
    harness.enable_cache(jax)
    job = harness.build_job(cell)
    rows = {"program": [], "control": []}

    def emit(kind, seed, numbers, extra=None):
        rec = {"kind": kind, "seed": seed, **numbers, **(extra or {})}
        print(json.dumps(rec), flush=True)
        rows.setdefault(kind, []).append(numbers)

    for seed in sorted(set(args.seeds) | set(args.control_seeds)
                       | set(args.witness_seeds)):
        numbers, (params0, warm, batches, ref), diag = program_reading(
            jax, job, cell, seed)
        if seed in args.seeds:
            emit("program", seed, numbers, diag)
        if seed in args.control_seeds:
            ctl = check.reference(jax, job, cell, params0, warm, batches,
                                  dtype=jnp.bfloat16, precision="default")
            emit("control", seed, check.compare(
                jax, ctl, ref, params0, warm,
                check.model_values(jax, job, warm, batches, ctl)))
        if seed in args.witness_seeds:
            wit = check.reference(jax, job, cell, params0, warm, batches,
                                  precision="default")
            emit("witness", seed, check.compare(
                jax, wit, ref, params0, warm,
                check.model_values(jax, job, warm, batches, wit)))
    for name in faults.FAULTS:
        fjob = faults.FAULTS[name](job)
        for seed in args.fault_seeds:
            numbers, _, _ = program_reading(jax, fjob, cell, seed)
            emit("fault:" + name, seed, numbers)
    summary = {}
    for kind, recs in rows.items():
        if not recs:
            continue
        pick = max if kind in ("program", "witness") else min
        summary[kind] = {k: pick(r[k] for r in recs) for k in recs[0]}
    print(json.dumps({"summary": summary}))


if __name__ == "__main__":
    main()
