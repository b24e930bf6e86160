"""Record one traced HF step of a cell on the chip, for the trace reducer's
tests (``bench/tests/fixtures/``).

  python3 bench/record_trace.py --workload timit-b16k --seed 1 --out <dir>

Sets up as a benchmark run does (``bench/harness.py``: data, weights, the
compiled programs, the ``warm_steps`` set-up steps), then traces one more
step and writes to ``<dir>``:

* ``<workload>_one_step.xplane.pb.gz``: the profiler's XSpace, gzipped;
* ``<workload>_one_step.ops.json``: ``{instruction: op_name}`` for every
  instruction of the compiled step that ran as an operation in the trace,
  read from the compiled step's HLO text, a source apart from the trace's
  own ``tf_op`` stat.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, phases, trace_reduce as tr  # noqa: E402

HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?(%[\w.\-]+) = .*?metadata=\{[^}]*op_name="([^"]*)"', re.M)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    import jax

    peaks = harness.load_json(os.path.join(harness.BENCH, "peaks.json"))
    harness.device_record(jax, cell.chips, peaks)
    harness.enable_cache(jax)
    job = harness.build_job(cell)
    _, drv, _, _ = harness.start(jax, job, cell, args.seed)
    trace_dir = os.path.join(args.out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    drv.one(time.perf_counter())
    jax.profiler.stop_trace()

    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, f"{args.workload}_one_step")
    with open(tr.find_xplane(trace_dir), "rb") as f:
        data = f.read()
    with gzip.open(stem + ".xplane.pb.gz", "wb") as f:
        f.write(data)
    ran = {o.name.partition(" = ")[0] for ops in phases.read_ops(data).values()
           for o in ops if o.module == phases.STEP_MODULE}
    names = dict(HLO_OP_NAME.findall(drv.step_c.as_text()))
    with open(stem + ".ops.json", "w") as f:
        json.dump({k: names[k] for k in sorted(ran) if k in names}, f,
                  indent=0, sort_keys=True)
    print(json.dumps({"xplane_bytes": len(data), "step_ops": len(ran),
                      "with_op_name": sum(k in names for k in ran)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
