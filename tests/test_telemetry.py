"""Telemetry subsystem (repro.obs): sink, zero-cost-off, phase/collective
events, Krylov introspection, trace merging, report CLI, and the headline
measurement — the overlapped schedule's grad-reduce span visibly
overlapping the curvature primal build, while the blocking schedule's does
not.

Fast tests run single-process (XLA:CPU runs debug callbacks synchronously
in the compute thread, so the executor's schedule is visible without a
real interconnect). The 2-process CLI test is slow-marked like the other
multiproc spawns.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HFConfig, hf_init
from repro.core.collectives import count_executed, jaxpr_collective_counts
from repro.core.distributed import data_parallel_hf_step
from repro.core.hf import METRICS_SCHEMA
from repro.core.solvers import cg
from repro.data import classification_dataset
from repro.models import build_mlp
from repro.obs import report, telemetry, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- sink --
def test_sink_roundtrip(tmp_path):
    d = str(tmp_path)
    with telemetry.Telemetry(d, process_index=3, meta={"kind": "t"}) as s:
        with s.span("outer", step=1):
            s.instant("hello", x=2)
        s.counter("depth", 4)
        s.collective_begin("g", "g")
        s.collective_begin("g", "g")   # FIFO: two in flight, same key
        s.collective_end("g", "g")
        s.collective_end("g", "g")
        s.solve_event(0, iters=3, residual=0.5)
    evs = trace.load_events(d)
    assert all(e["pid"] == 3 for e in evs)
    kinds = [e["ev"] for e in evs]
    assert kinds[0] == "meta" and evs[0]["kind"] == "t"
    colls = [e for e in evs if e["ev"] == "coll"]
    assert len(colls) == 2
    assert all(c["t1"] >= c["t0"] for c in colls)
    # FIFO pairing: first end takes the first begin
    assert colls[0]["t0"] <= colls[1]["t0"]
    span = next(e for e in evs if e["ev"] == "span")
    assert span["t1"] >= span["t0"] and span["step"] == 1


# ---------------------------------------------- instrumented step fixture --
@pytest.fixture(scope="module")
def setup():
    model = build_mlp((16, 32, 4))
    params = model.init(jax.random.PRNGKey(1))
    data = classification_dataset(jax.random.PRNGKey(0), 16, 16, 4)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    return model, params, data, mesh


@pytest.fixture(scope="module")
def instrumented_run(setup, tmp_path_factory):
    """One jitted s-step data-parallel HF step with sink + executed-count
    instrumentation armed; shared by the event-content tests below."""
    model, params, data, mesh = setup
    cfg = HFConfig(solver="hessian_cg", max_cg_iters=6, cg_tol=0.0,
                   sstep_s=2)
    d = str(tmp_path_factory.mktemp("telemetry"))
    sink = telemetry.Telemetry(d)
    with telemetry.install(sink), count_executed() as counts:
        step = data_parallel_hf_step(model.loss_fn, mesh, cfg)
        p, s, m = jax.jit(step)(params, hf_init(params, cfg), data)
        jax.block_until_ready(p)
    sink.close()
    executed = counts.per_device(len(jax.local_devices()))
    return d, trace.load_events(d), executed, jax.device_get(m)


# ------------------------------------------------------- zero-cost off --
def test_zero_cost_when_disabled(setup, tmp_path):
    """No sink installed → the jaxpr carries no callbacks and the static
    collective fingerprint is byte-identical to the audited one; installed
    → callbacks appear WITHOUT changing the collective schedule."""
    model, params, data, mesh = setup
    cfg = HFConfig(solver="hessian_cg", max_cg_iters=8, cg_tol=0.0)

    step_off = data_parallel_hf_step(model.loss_fn, mesh, cfg)
    jx_off = jax.make_jaxpr(step_off)(params, hf_init(params, cfg), data)
    assert "callback" not in str(jx_off)
    c_off = jaxpr_collective_counts(jx_off.jaxpr)
    # hessian_cg_s1 fingerprint from tests/test_collective_audit.py COMBOS
    assert (c_off["top"]["psum"], c_off["while_body"]["psum"]) == (11, 6)

    with telemetry.Telemetry(str(tmp_path)) as sink:
        with telemetry.install(sink):
            step_on = data_parallel_hf_step(model.loss_fn, mesh, cfg)
            jx_on = jax.make_jaxpr(step_on)(params, hf_init(params, cfg),
                                            data)
    assert "callback" in str(jx_on)
    c_on = jaxpr_collective_counts(jx_on.jaxpr)
    assert (c_on["top"]["psum"], c_on["while_body"]["psum"]) == (11, 6)


# ------------------------------------------------------ event content --
def test_collective_events_match_executed_counts(instrumented_run):
    """Per tag, the telemetry begin/end span pairs count exactly the
    collectives the independent executed-count callback tallies."""
    _, events, executed, _ = instrumented_run
    colls = trace.collective_spans(events)
    by_tag = {}
    for c in colls:
        by_tag[c["tag"]] = by_tag.get(c["tag"], 0) + 1
        assert c["t1"] >= c["t0"]
    assert by_tag == {t: int(n) for t, n in executed.items() if n}


def test_phase_markers_present_and_ordered(instrumented_run):
    _, events, _, _ = instrumented_run
    spans = trace.phase_spans(events)
    names = [s["name"] for s in spans if s["step"] == 0]
    # shared-primal path: no separate grad_build phase
    assert names == ["curvature_primal", "krylov_solve", "line_search",
                     "update_damping"]
    ts = [s["t1"] for s in spans if s["step"] == 0]
    assert ts == sorted(ts)
    assert all(s["t1"] >= s["t0"] for s in spans)


def test_solve_event_matches_metrics(instrumented_run):
    _, events, _, m = instrumented_run
    (sol,) = [e for e in events if e["ev"] == "solve"]
    assert sol["step"] == 0
    assert sol["iters"] == int(m["cg_iters"])
    assert sol["syncs"] == int(m["krylov_syncs"])
    assert sol["residual"] == pytest.approx(float(m["cg_residual"]),
                                            rel=1e-5)
    hist = sol["residual_history"]
    assert len(hist) == sol["iters"]           # NaN tail filtered
    assert all(np.isfinite(hist))
    assert hist[-1] == pytest.approx(float(m["cg_residual"]), rel=1e-5)


def test_metrics_contract(instrumented_run):
    """Every hf_step metric: enumerated in METRICS_SCHEMA, scalar, finite."""
    _, _, _, m = instrumented_run
    assert set(m) == set(METRICS_SCHEMA)
    for k, v in m.items():
        arr = np.asarray(v)
        assert arr.shape == (), (k, arr.shape)
        assert np.isfinite(arr.astype(np.float64)), (k, v)


# ------------------------------------------- solver residual history --
def test_residual_history_solver_level():
    """cg's residual_history: ‖r‖ per executed iteration, NaN beyond."""
    n = 12
    diag = jnp.linspace(1.0, 4.0, n)
    A = lambda v: diag * v  # noqa: E731
    b = jnp.ones((n,))
    res = cg(A, b, jnp.zeros((n,)), lam=0.0, max_iters=20, tol=1e-6)
    it = int(res.iters)
    hist = np.asarray(res.residual_history)
    assert hist.shape == (20,)
    assert np.all(np.isfinite(hist[:it]))
    assert np.all(np.isnan(hist[it:]))
    assert hist[it - 1] == pytest.approx(float(res.residual), rel=1e-5)
    # monotone-ish convergence on an SPD diagonal: last < first
    assert hist[it - 1] < hist[0]


# ------------------------------------------------- trace.json merging --
def _synthetic_events():
    return [
        {"ev": "meta", "pid": 0, "process": 0, "ts": 100.0},
        {"ev": "phase", "pid": 0, "name": "step_begin", "step": 0,
         "ts": 100.0},
        {"ev": "phase", "pid": 0, "name": "grad_build", "step": 0,
         "ts": 100.1},
        {"ev": "phase", "pid": 0, "name": "curvature_primal", "step": 0,
         "ts": 100.4},
        {"ev": "coll", "pid": 0, "tag": "grad_hvp", "label": "grad_reduce",
         "t0": 100.15, "t1": 100.35},
        {"ev": "coll", "pid": 1, "tag": "grad_hvp", "label": "grad_reduce",
         "t0": 100.0, "t1": 100.05},
        {"ev": "phase", "pid": 1, "name": "step_begin", "step": 0,
         "ts": 99.9},
        {"ev": "phase", "pid": 1, "name": "grad_reduce", "step": 0,
         "ts": 100.05},
        {"ev": "phase", "pid": 1, "name": "curvature_primal", "step": 0,
         "ts": 100.3},
        {"ev": "counter", "pid": 0, "name": "loss", "value": 2.0,
         "ts": 100.4},
        {"ev": "span", "pid": 0, "name": "host_step", "t0": 100.0,
         "t1": 100.5, "step": 0},
    ]


def test_overlap_math_on_synthetic_events():
    evs = _synthetic_events()
    assert trace.overlap_seconds(dict(t0=0.0, t1=2.0),
                                 dict(t0=1.0, t1=3.0)) == 1.0
    assert trace.overlap_seconds(dict(t0=0.0, t1=1.0),
                                 dict(t0=2.0, t1=3.0)) == 0.0
    rows = trace.grad_reduce_overlap(evs)
    by_pid = {r["pid"]: r for r in rows}
    # pid 0: coll [.15,.35] vs curvature_primal [.1,.4] → 0.2s overlap
    assert by_pid[0]["overlap_s"] == pytest.approx(0.2, abs=1e-9)
    # pid 1 (blocking): coll closed at the phase's left edge → zero
    assert by_pid[1]["overlap_s"] == pytest.approx(0.0, abs=1e-9)


def test_phase_closes_after_its_collective():
    """A blocking grad_reduce marker that reaches the host before its
    collective's end callback (both wait on the reduced value) closes the
    phase at the collective's end, of its own step only."""
    evs = [
        {"ev": "phase", "pid": 0, "name": "step_begin", "step": 3, "ts": 1.0},
        {"ev": "phase", "pid": 0, "name": "grad_build", "step": 3, "ts": 2.0},
        {"ev": "phase", "pid": 0, "name": "grad_reduce", "step": 3,
         "ts": 2.5},
        {"ev": "phase", "pid": 0, "name": "curvature_primal", "step": 3,
         "ts": 4.0},
        {"ev": "coll", "pid": 0, "tag": "grad_hvp", "label": "grad_reduce",
         "step": 3, "t0": 2.1, "t1": 2.6},
        {"ev": "coll", "pid": 0, "tag": "grad_hvp", "label": "grad_reduce",
         "step": 4, "t0": 9.0, "t1": 9.5},
    ]
    spans = {s["name"]: s for s in trace.phase_spans(evs)}
    assert (spans["grad_reduce"]["t0"], spans["grad_reduce"]["t1"]) == (
        2.0, 2.6)
    assert spans["curvature_primal"]["t0"] == 2.6
    (row,) = trace.grad_reduce_overlap(evs)
    assert row["overlap_s"] == 0.0


def test_blocking_step_adds_nothing_when_disabled(setup):
    """No sink: the blocking schedule's standalone gradient reduce brings
    no callback and no optimization barrier into the step; the order of
    reduce and curvature primal is XLA's, as with no telemetry at all."""
    model, params, data, mesh = setup
    cfg = HFConfig(solver="hessian_cg", max_cg_iters=4, cg_tol=0.0)
    step = data_parallel_hf_step(model.loss_fn, mesh, cfg, hvp_frac=0.5)
    jx = str(jax.make_jaxpr(step)(params, hf_init(params, cfg), data))
    assert "callback" not in jx and "optimization_barrier" not in jx


def test_build_trace_structure(tmp_path):
    d = str(tmp_path)
    with open(os.path.join(d, "events-p0.jsonl"), "w") as f:
        for e in _synthetic_events():
            if e.get("pid") == 0:
                f.write(json.dumps({k: v for k, v in e.items()
                                    if k != "pid"}) + "\n")
    out = trace.merge_dir(d)
    assert os.path.basename(out) == "trace.json"
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    phases = [e for e in evs if e.get("ph") == "X"]
    assert phases, evs
    assert all(e["ts"] >= 0 and e["dur"] >= 1 for e in phases)
    assert {e["ph"] for e in evs} >= {"X", "M", "C"}
    names = {e["name"] for e in phases}
    assert {"grad_build", "curvature_primal", "grad_reduce",
            "host_step"} <= names


# -------------------------------------------------------- report CLI --
def test_report_renders_real_run(instrumented_run, capsys):
    d, _, _, _ = instrumented_run
    summary = report.render(d)
    out = capsys.readouterr().out
    assert summary["n_phases"] > 0
    assert summary["n_collectives"] > 0
    assert summary["n_solves"] == 1
    for section in ("phase breakdown", "collective timeline",
                    "solve convergence"):
        assert section in out, out
    assert report.main([d, "--check"]) == 0


def test_report_check_fails_on_empty(tmp_path, capsys):
    d = str(tmp_path)
    with telemetry.Telemetry(d):
        pass                                   # meta only, no phases
    assert report.main([d, "--check"]) == 1


# ---------------------------------- the schedule measurement (headline) --
def _overlap_run(overlap: bool, out_dir: str):
    """One non-shared-primal HF step (hvp_frac<1 ⇒ the gradient reduce is a
    standalone collective) big enough that the curvature primal build is
    long against callback granularity. Returns the loaded events."""
    model = build_mlp((64, 256, 256, 10))
    params = model.init(jax.random.PRNGKey(1))
    data = classification_dataset(jax.random.PRNGKey(0), 256, 64, 10)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    cfg = HFConfig(solver="hessian_cg", max_cg_iters=4, cg_tol=0.0,
                   overlap=overlap)
    sink = telemetry.Telemetry(out_dir)
    with telemetry.install(sink):
        step = data_parallel_hf_step(model.loss_fn, mesh, cfg,
                                     hvp_frac=0.5)
        p, s, m = jax.jit(step)(params, hf_init(params, cfg), data)
        jax.block_until_ready(p)
    sink.close()
    return trace.load_events(out_dir)


def _primal_and_reduce(events):
    (primal,) = [s for s in trace.phase_spans(events)
                 if s["name"] == "curvature_primal"]
    (red,) = [c for c in trace.collective_spans(events)
              if c["label"] == "grad_reduce"]
    return primal, red


def test_hidden_reduce_schedule_single_process(tmp_path):
    """Single-process edition of the schedule measurement (a 1-device psum
    is ~free, so the honest single-process observable is the *ordering*,
    not the duration): blocking mode pins the grad-reduce before the
    curvature primal build — its span closes before the build starts and
    an explicit grad_reduce phase appears; overlap mode removes that
    ordering — the reduce executes at/after the build's start and the
    grad_reduce phase is gone. The duration-overlap assertion (reduce span
    bracketing the primal at ~full width) lives in the 2-process test
    below, where gloo gives the collective real latency."""
    evs_ov = _overlap_run(True, str(tmp_path / "ov"))
    evs_bl = _overlap_run(False, str(tmp_path / "bl"))

    p_bl, r_bl = _primal_and_reduce(evs_bl)
    assert any(s["name"] == "grad_reduce" for s in trace.phase_spans(evs_bl))
    assert r_bl["t1"] <= p_bl["t0"], (r_bl, p_bl)
    rows_bl = trace.grad_reduce_overlap(evs_bl)
    assert rows_bl and all(r["overlap_s"] == 0 for r in rows_bl), rows_bl

    p_ov, r_ov = _primal_and_reduce(evs_ov)
    assert not any(s["name"] == "grad_reduce"
                   for s in trace.phase_spans(evs_ov))
    assert r_ov["t0"] >= p_ov["t0"], (r_ov, p_ov)


@pytest.mark.slow  # 2× (2-process spawn + jit train loop): ~2 min
def test_two_process_trace_shows_overlap(tmp_path):
    """`train --num-processes 2 --telemetry-dir D`: the primary merges one
    trace.json whose per-process grad-reduce spans overlap the curvature
    primal under --overlap and do not without it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)

    def run(overlap: bool, d: str):
        cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
               "qwen1.5-0.5b", "--smoke", "--num-processes", "2",
               "--steps", "2", "--batch-size", "8", "--seq-len", "16",
               "--max-cg-iters", "4", "--sstep", "2",
               "--telemetry-dir", d]
        if overlap:
            cmd.append("--overlap")
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           cwd=ROOT, timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr
        assert os.path.exists(os.path.join(d, "trace.json"))
        evs = trace.load_events(d)
        assert {e["pid"] for e in evs} == {0, 1}
        return trace.grad_reduce_overlap(evs)

    rows_ov = run(True, str(tmp_path / "ov"))
    rows_bl = run(False, str(tmp_path / "bl"))
    for pid in (0, 1):
        ov = [r for r in rows_ov if r["pid"] == pid]
        bl = [r for r in rows_bl if r["pid"] == pid]
        assert ov and bl, (rows_ov, rows_bl)
        # steady-state steps (step 0 includes warm caches); require the
        # hidden reduce to overlap the primal on every step for overlap
        # mode and on none for blocking mode
        assert all(r["overlap_s"] > 0 for r in ov), rows_ov
        assert all(r["overlap_s"] == 0 for r in bl), rows_bl
