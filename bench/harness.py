"""One run of one benchmark cell.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration's file (its ``file`` entry) and its module beside it
(``bench/configs/<config>.py``: weights, the plain reference, the FLOP
count), the program side of its family (``bench/families/<family>.py``),
the traffic file (``bench/traffic/<traffic>.json``), the cell's limits
(``bench/limits/<cell>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``). The peaks are ``bench/peaks.json``, keyed
by ``device_kind``.

A run:

1. refuses to run unless JAX's first device is a TPU whose ``device_kind``
   the peaks table holds, and the chips the cell asks for are there;
2. keeps JAX's persistent compilation cache inside the checkout
   (``repro.launch.cache.enable_compile_cache``);
3. makes the data and the weights on the device from ``--seed``;
4. compiles the draw, the HF step and the held-out evaluation at the
   cell's shapes, then drives the one compiled step through its first
   ``warm_steps`` steps (its warm-up, and the steps the correctness check
   compares: ``bench/check.py``) and hands it on to the window;
5. trains for ``--seconds``: each step draws its batch on the device,
   calls the step, pulls the step's metrics once; every ``eval_every``
   steps the held-out loss is computed and left on the device;
6. counts compilations inside the window (there must be none);
7. checks the set-up steps against the plain reference and prints the
   result as its last line of standard output.

With ``--trace 1`` the same window runs with the profiler on for
``trace_steps`` steps, and the per-layer metrics are read from that trace.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from typing import Any, NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def log(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files --
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with every file it names."""

    def __init__(self, name: str, root: str = ROOT):
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in entries:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = entries[name]
        self.chips = self.entry["chips"]
        cfgs = {c["name"]: c for c in self.manifest["configs"]}
        cfg_entry = cfgs[self.entry["config"]]
        self.cfg = load_json(os.path.join(root, cfg_entry["file"]))
        base = os.path.splitext(os.path.join(root, cfg_entry["file"]))[0]
        self.cfg_module = load_module(base + ".py", "bench_cfg_" + cfg_entry["name"])
        self.traffic = load_json(os.path.join(
            root, "bench", "traffic", self.entry["traffic"] + ".json"))
        self.limits = load_json(os.path.join(root, "bench", "limits",
                                             name + ".json"))
        self.end_to_end = [m for m in self.manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in self.manifest["per_layer"]
                          if name in m.get("workloads", [name])]

    def family(self):
        import importlib
        return importlib.import_module(f"bench.families.{self.cfg['family']}")

    def reader(self, metric: str):
        return load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))


# ----------------------------------------------------------------- device --
def device_record(jax, chips: int, peaks: dict, require_tpu: bool = True):
    """The device as JAX reports it; exits non-zero without the chips the
    cell asks for, or without a peak for the chip."""
    devs = jax.devices()
    dev = devs[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise SystemExit(f"bench: no TPU (JAX platform {dev.platform!r})")
        if dev.device_kind not in peaks:
            raise SystemExit(f"bench: no peaks for device kind "
                             f"{dev.device_kind!r} in bench/peaks.json")
        if len(devs) < chips:
            raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                             f"found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def peak_bytes(jax, devices) -> int:
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


class CompileCounter:
    """Counts compilation events while ``armed`` (jax.monitoring)."""

    def __init__(self, jax):
        self.jax = jax
        self.armed = False
        self.in_window = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self):
        self.jax.monitoring.unregister_event_duration_listener(self._duration)
        self.jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if self.armed and "compile" in event:
            self.in_window += 1

    def _event(self, event, **_):
        key = event.rsplit("/", 1)[-1].replace("cache_", "")
        if event.startswith("/jax/compilation_cache/") and key in self.cache:
            self.cache[key] += 1


# ------------------------------------------------------------------- steps --
class Driver:
    """The compiled draw, step and held-out evaluation, and the loop that
    calls them. Set-up and the window go through the same ``one``."""

    def __init__(self, jax, job, data, params, state, eval_every):
        from jax.profiler import TraceAnnotation

        self.jax, self.data = jax, data
        self.params, self.state = params, state
        self.eval_every = eval_every
        self.Ann = TraceAnnotation
        self.i = 0
        self.steps = []                # host metrics of every step
        self.evals = []                # (step, t_done, device scalar)
        import numpy as np
        self.np = np
        i0 = np.int32(0)
        self.draw_c = job.draw.lower(data, i0).compile()
        batch = self.draw_c(data, i0)
        self.step_c = jax.jit(job.opt.step).lower(params, state, batch).compile()
        self.eval_c = (job.heldout_loss.lower(params, data).compile()
                       if job.heldout_loss is not None and eval_every else None)

    def one(self, t0):
        """One outer step; returns the host time at which its metrics came
        back, relative to ``t0``."""
        jax, Ann = self.jax, self.Ann
        with Ann("hf_step"):
            with Ann("batch_draw"):
                batch = self.draw_c(self.data, self.np.int32(self.i))
            with Ann("dispatch"):
                self.params, self.state, m = self.step_c(
                    self.params, self.state, batch)
            with Ann("metric_pull"):
                m = jax.device_get(m)
            t_done = time.perf_counter() - t0
            if self.eval_c is not None and (self.i + 1) % self.eval_every == 0:
                with Ann("heldout_eval"):
                    self.evals.append(
                        (self.i, t_done, self.eval_c(self.params, self.data)))
        self.steps.append({k: float(v) for k, v in m.items()})
        self.i += 1
        return t_done


# --------------------------------------------------------------- the run --
def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_process=None):
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse(argv)
    cell = Cell(args.workload)
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    import jax

    device = device_record(jax, cell.chips, peaks)
    result = run(jax, cell, args.seed, args.seconds, bool(args.trace),
                 device, peaks[device["kind"]], t_process)
    print(json.dumps(result))
    return 0


def enable_cache(jax):
    """JAX's persistent compilation cache inside the checkout, keeping every
    program, so that only a cell's first run in a checkout compiles."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def build_job(cell, fault=None):
    job = cell.family().build(cell.cfg, cell.traffic, cell.cfg_module,
                              cell.chips)
    return job if fault is None else fault(job)


class Warm(NamedTuple):
    """What set-up keeps, on the host, for the correctness check."""
    metrics: list         # host metrics of every set-up step
    free_params: Any      # parameters after the first check.FREE_STEPS steps
    steady: list          # per compared steady step: its index, params and
                          # HF state before it, params after it, its metrics


def start(jax, job, cell, seed):
    """Data and weights from the seed, the compiled programs, and the first
    ``traffic["warm_steps"]`` steps. Returns (k_params, driver, Warm,
    timings)."""
    from bench import check, generate

    t_init = time.perf_counter()
    k_data, k_params = jax.random.split(generate.seed_key(seed))
    data = jax.block_until_ready(job.make_data(k_data))
    t_data = time.perf_counter()
    params = job.make_params(k_params)
    state = jax.block_until_ready(job.opt.init(params))
    drv = Driver(jax, job, data, params, state,
                 cell.traffic.get("eval_every", 0))
    t_compile = time.perf_counter()
    n_warm = cell.traffic["warm_steps"]
    first_steady = n_warm - check.STEADY_STEPS
    if first_steady < check.FREE_STEPS:
        raise ValueError(f"warm_steps {n_warm} leaves no steady step to compare")
    free_params, steady = None, []
    for i in range(n_warm):
        if i >= first_steady:
            before = jax.device_get((drv.params, drv.state))
        drv.one(t_compile)
        if i == check.FREE_STEPS - 1:
            free_params = jax.device_get(drv.params)
        if i >= first_steady:
            steady.append({"step": i, "params": before[0], "state": before[1],
                           "after": jax.device_get(drv.params),
                           "metrics": drv.steps[-1]})
    warm = Warm(list(drv.steps), free_params, steady)
    if drv.eval_c is not None:
        jax.block_until_ready(drv.eval_c(drv.params, drv.data))
    drv.evals.clear()
    drv.steps.clear()
    times = {"t_init": t_init, "data_s": t_data - t_init,
             "compile_s": t_compile - t_data,
             "warmup_s": time.perf_counter() - t_compile}
    return k_params, drv, warm, times


def compared_batches(jax, drv, warm):
    """The batches of the compared set-up steps, on the host, in the order
    of ``check.side``."""
    from bench import check

    steps = list(range(check.FREE_STEPS)) + [s["step"] for s in warm.steady]
    return [jax.device_get(drv.draw_c(drv.data, drv.np.int32(i)))
            for i in steps]


def run(jax, cell, seed, seconds, trace, device, peak, t_process,
        fault=None):
    """Set up, measure, check. ``fault`` (tests only) wraps the program's
    job to plant a fault under the timed path."""
    cache_dir = enable_cache(jax)
    counter = CompileCounter(jax)
    job = build_job(cell, fault)
    devices = jax.devices()[:cell.chips]
    k_params, drv, warm, times = start(jax, job, cell, seed)
    t_window = time.perf_counter()
    setup = {"import_init_s": times["t_init"] - t_process,
             "data_s": times["data_s"], "compile_s": times["compile_s"],
             "warmup_s": times["warmup_s"]}
    log(f"cache {cache_dir} {counter.cache}; set-up " +
        " ".join(f"{k} {v:.3f}" for k, v in setup.items()))

    # ---------------------------------------------------------- window --
    trace_steps = cell.traffic.get("trace_steps", 5) if trace else 0
    trace_dir = os.path.join(ROOT, ".bench_out", "trace", cell.name)
    traced, tracing = [], False
    counter.armed = True
    t0 = time.perf_counter()
    t_end = 0.0
    while t_end < seconds:
        if trace_steps and not tracing and not traced and t_end >= seconds / 3:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            tracing = True
        t_end = drv.one(t0)
        if tracing:
            traced.append(len(drv.steps) - 1)
            if len(traced) == trace_steps:
                jax.profiler.stop_trace()
                tracing = False
    if tracing:
        jax.profiler.stop_trace()
    counter.armed = False
    counter.close()
    window_s = t_end
    steps = drv.steps
    n_steps = len(steps)
    failed = sum(1 for m in steps if m["step_rejected"] or
                 not (abs(m["loss_new"]) < float("inf")))
    mem = peak_bytes(jax, devices)
    evals = [(i, t, float(v)) for i, t, v in drv.evals]
    log(f"window {window_s:.3f}s, {n_steps} steps, compiles in window "
        f"{counter.in_window}, failed {failed}, peak {mem} B")
    log("cg_iters mean {:.3f}, ls_evals mean {:.3f}, loss {:.5f} -> {:.5f}"
        .format(statistics.fmean(m["cg_iters"] for m in steps),
                statistics.fmean(m["ls_evals"] for m in steps),
                steps[0]["loss"], steps[-1]["loss_new"]))
    if evals:
        log("held-out " + " ".join(f"{i}:{t:.2f}s:{v:.5f}" for i, t, v in evals))

    # ------------------------------------------------------- metrics --
    metrics, breakdown, dev = {}, None, dict(device)
    dev["memory_peak_bytes"] = mem
    if trace:
        from bench import trace_reduce as tr
        trace_obj = tr.load(tr.find_xplane(trace_dir))
        lo, hi = tr.window(trace_obj)
        ids = [d.id for d in devices]
        busy = [tr.busy_ns(trace_obj, i, lo, hi) for i in ids]
        if dev["platform"] == "tpu" and not all(busy):
            raise RuntimeError(f"the trace holds no device operation inside "
                               f"its window on some of devices {ids}: {busy}")
        dev["busy_s"] = statistics.fmean(busy) * 1e-9
        dev["window_s"] = (hi - lo) * 1e-9
        ctx = {"trace": trace_obj, "lo": lo, "hi": hi, "devices": ids,
               "steps": steps, "traced_steps": [steps[i] for i in traced],
               "job": job, "cfg": cell.cfg, "traffic": cell.traffic,
               "peak": peak, "chips": cell.chips}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(trace_obj, ids[0], lo, hi),
                     "idle_gaps": tr.top_gaps(trace_obj, ids[0], lo, hi)}
    else:
        e2e = {"step_ms": window_s / n_steps * 1e3,
               "setup_s": t_window - t_process}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # --------------------------------------------------------- correct --
    from bench import check
    batches = compared_batches(jax, drv, warm)
    del drv
    checks = check.training(jax, job, cell, k_params, warm, batches)
    correct = (counter.in_window == 0 and
               all(c["value"] <= c["limit"] for c in checks.values()))
    checks["compiles_in_window"] = {"value": counter.in_window, "limit": 0}
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    out = {"correct": bool(correct), "attempted": n_steps, "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
