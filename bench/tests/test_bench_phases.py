"""Device time by phase (``bench/phases.py``): the attribution on synthetic
intervals, the XSpace reading against ``trace_reduce``'s, and the readers on
a step of ``timit-b16k`` recorded on a TPU v5e with the phase scopes."""
import gzip
import importlib.util
import json
import os

import pytest

from bench import phases as ph
from bench import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
OLD_STEP = os.path.join(FIXTURES, "tpu_v5e_one_step.xplane.pb.gz")
# One traced step of the harness on timit-b16k at full size, on a TPU v5e
# (bench/record_trace.py), and {instruction: op_name} of the ops that ran,
# from the compiled step's HLO text.
B16K_STEP = os.path.join(FIXTURES, "timit-b16k_one_step.xplane.pb.gz")
B16K_OPS = os.path.join(FIXTURES, "timit-b16k_one_step.ops.json")
PHASES = ("grad_build", "grad_reduce", "curvature_primal", "krylov_solve",
          "curvature_product", "direction", "line_search", "update_damping")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_program_vocabulary_is_the_one_tested_here():
    assert ph.program_phases() == PHASES


@pytest.mark.parametrize("op_name,path", [
    ("jit(step)/krylov_solve/while/body/curvature_product/jvp(jvp())/dot_general:",
     ("krylov_solve", "curvature_product")),
    ("jit(step)/transpose(jvp(grad_build))/mul", ("grad_build",)),
    ("jit(step)/line_search/while/body/jit(log_softmax)/exp", ("line_search",)),
    ("jit(step)/mul", ()),
    (None, ()),
])
def test_phase_path(op_name, path):
    assert ph.phase_path(op_name, PHASES) == path


def test_own_time_goes_to_the_innermost_operation():
    # a loop [0, 100) with two body ops, one op after it, one overlapping
    # its end; the window cuts [5, 130)
    ivs = [(0, 100, "loop"), (10, 30, "a"), (50, 60, "b"), (95, 110, "c"),
           (120, 140, "d")]
    got = ph.own_ns(ivs, 5, 130)
    assert got == {"loop": 5 + 20 + 35, "a": 20, "b": 10, "c": 15, "d": 10}
    union = tr.union([(k, s, e) for s, e, k in ivs])
    assert sum(got.values()) == tr.covered(tr.clip(union, 5, 130))


def test_own_time_of_ops_starting_together_goes_to_the_shorter():
    assert ph.own_ns([(0, 10, "outer"), (0, 4, "inner")], 0, 10) == {
        "inner": 4, "outer": 6}


def op(start, end, op_name, module="jit_step"):
    return ph.Op(start, end, module, op_name, "%x = f32[] x()")


def synthetic_step():
    """One step: gradient, a Krylov loop holding two products and a
    recurrence op, a line-search loop, an unscoped copy, and an op of
    another module."""
    k = "jit(step)/krylov_solve/while"
    return [op(0, 10, "jit(step)/grad_build/jvp()/dot_general"),
            op(10, 12, "jit(step)/curvature_primal/jvp()/dot_general"),
            op(12, 60, k),
            op(14, 30, k + "/body/curvature_product/jvp(jvp())/dot_general"),
            op(30, 34, k + "/body/mul"),
            op(34, 50, k + "/body/curvature_product/jvp(jvp())/dot_general"),
            op(60, 62, None),
            op(62, 80, "jit(step)/line_search/while"),
            op(64, 78, "jit(step)/line_search/while/body/dot_general"),
            op(80, 90, "jit(heldout_loss)/dot_general", "jit_heldout_loss")]


def test_ops_without_a_name_take_their_loops_phases():
    """A loop op the trace leaves unnamed takes the phases its body shares;
    an unnamed copy in the body takes the loop's; one outside stays
    unscoped."""
    k = "jit(step)/krylov_solve/while/body"
    ops = [op(0, 50, None),
           op(5, 10, k + "/curvature_product/dot_general"),
           op(10, 12, None),
           op(12, 20, k + "/mul"),
           op(60, 62, None)]
    assert ph.paths(ops, PHASES) == [
        ("krylov_solve",), ("krylov_solve", "curvature_product"),
        ("krylov_solve",), ("krylov_solve",), ()]


def test_split_nests_products_in_the_solve():
    s = ph.split(synthetic_step(), 0, 100, PHASES)
    assert s.busy_ns == 80
    assert s.inclusive == {"grad_build": 10, "curvature_primal": 2,
                           "krylov_solve": 48, "curvature_product": 32,
                           "line_search": 18}
    assert s.own == {"grad_build": 10, "curvature_primal": 2,
                     "krylov_solve": 16, "curvature_product": 32,
                     "line_search": 18, None: 2}
    assert sum(s.own.values()) == s.busy_ns


def write_trace(tmp_path, gz):
    """A fixture where the harness writes its traces, and its reading."""
    d = tmp_path / ".bench_out" / "trace" / "cell" / "plugins"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    with gzip.open(gz, "rb") as f:
        path.write_bytes(f.read())
    return tr.load(str(path))


def test_reading_matches_trace_reduce(tmp_path):
    t = write_trace(tmp_path, OLD_STEP)
    with gzip.open(OLD_STEP, "rb") as f:
        ops = ph.read_ops(f.read())
    assert list(ops) == [0]
    assert [(o.name, o.start, o.end) for o in ops[0]] == t.device_ops[0]
    assert {o.module for o in ops[0]} == {"jit_draw", "jit_step",
                                          "jit_heldout_loss"}
    assert ph.find_xplane(t, 0, str(tmp_path)).endswith("host.xplane.pb")


def ctx_of(t, steps=1):
    lo, hi = tr.window(t)
    return {"trace": t, "lo": lo, "hi": hi, "devices": [0], "chips": 1,
            "traced_steps": [{"cg_iters": 16.0, "ls_evals": 2.0}] * steps}


def test_a_program_without_phases_reads_nothing(tmp_path, monkeypatch):
    t = write_trace(tmp_path, OLD_STEP)
    monkeypatch.setattr(ph, "ROOT", str(tmp_path))
    monkeypatch.setattr(ph, "program_phases", lambda: None)
    assert reader("krylov_ms_per_step").read(ctx_of(t)) is None
    assert reader("curvature_product_mfu").read(ctx_of(t)) is None


def test_a_step_without_phases_is_refused(tmp_path, monkeypatch):
    """The program names phases, the trace's step carries none (the old
    fixture, recorded before the scopes): raise, never read 0."""
    t = write_trace(tmp_path, OLD_STEP)
    monkeypatch.setattr(ph, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError, match="carries a phase"):
        reader("grad_ms_per_step").read(ctx_of(t))


def test_a_trace_without_tpu_operations_reads_nothing():
    t = tr.Trace({}, [("hf_step", 0, 10)])
    assert reader("line_search_ms_per_step").read(ctx_of(t)) is None


@pytest.fixture(scope="module")
def b16k():
    """The recorded timit-b16k step: its reading, its ops, its op map."""
    with gzip.open(B16K_STEP, "rb") as f:
        data = f.read()
    with open(B16K_OPS) as f:
        op_map = json.load(f)
    return data, ph.read_ops(data)[0], op_map


def test_recorded_op_names_are_the_compiled_steps(b16k):
    """The trace's tf_op stat of each op of the step is the op_name the
    compiled step's HLO text gives that instruction; the trace leaves the
    two loops without one, and the HLO text puts them in their phases."""
    _, ops, op_map = b16k
    step = [o for o in ops if o.module == ph.STEP_MODULE]
    loops = [o for o in step if " while(" in o.name]
    named = [o for o in step if o.name.partition(" = ")[0] in op_map
             and o not in loops]
    assert len(named) > 1000
    for o in named:
        assert o.op_name and (o.op_name.rsplit(":", 1)[0]
                              == op_map[o.name.partition(" = ")[0]])
    assert loops and all(o.op_name is None for o in loops)
    assert {op_map[o.name.partition(" = ")[0]] for o in loops} == {
        "jit(step)/krylov_solve/while", "jit(step)/line_search/while"}


def test_recorded_phases_cover_the_step(b16k, tmp_path):
    """Named phases cover the step's busy time within 2%; the loops take
    the phases of their bodies."""
    data, ops, _ = b16k
    path = tmp_path / "step.xplane.pb"
    path.write_bytes(data)
    lo, hi = tr.window(tr.load(str(path)))
    s = ph.split(ops, lo, hi, PHASES)
    busy = tr.covered(tr.clip(tr.union(
        [(o.name, o.start, o.end) for o in ops
         if o.module == ph.STEP_MODULE]), lo, hi))
    assert s.busy_ns == busy > 0
    named = sum(ns for p, ns in s.own.items() if p is not None)
    assert named == pytest.approx(busy, rel=0.02)
    inc = s.inclusive
    assert inc["krylov_solve"] > inc["curvature_product"] > 0
    assert min(inc["grad_build"], inc["line_search"],
               inc["curvature_primal"]) > 0
    step = [o for o in ops if o.module == ph.STEP_MODULE]
    loops = {p for o, p in zip(step, ph.paths(step, PHASES))
             if " while(" in o.name}
    assert loops == {("krylov_solve",), ("line_search",)}


def krylov_iterations(ops):
    """Iterations of the solve, from the trace: each instruction of the
    Krylov loop's body runs once per iteration."""
    from collections import Counter

    body = Counter(o.name for o in ops if o.op_name
                   and "krylov_solve/while/body/" in o.op_name)
    return Counter(body.values()).most_common(1)[0][0]


def test_readers_on_the_recorded_step(b16k, tmp_path, monkeypatch):
    from bench import flops, harness

    data, ops, _ = b16k
    d = tmp_path / ".bench_out" / "trace" / "timit-b16k"
    d.mkdir(parents=True)
    (d / "step.xplane.pb").write_bytes(data)
    t = tr.load(str(d / "step.xplane.pb"))
    monkeypatch.setattr(ph, "ROOT", str(tmp_path))
    cell = harness.Cell("timit-b16k")

    class Job:
        @staticmethod
        def matmuls(rows):
            return cell.cfg_module.matmuls(cell.cfg, rows)

    iters = krylov_iterations(ops)
    assert 1 <= iters <= 16
    ctx = dict(ctx_of(t), job=Job, traffic=cell.traffic,
               traced_steps=[{"cg_iters": float(iters), "ls_evals": 2.0}],
               peak={"flops_per_s": 197e12})
    got = {m: reader(m).read(ctx) for m in (
        "grad_ms_per_step", "line_search_ms_per_step", "krylov_ms_per_step",
        "curvature_product_ms_per_step", "curvature_product_mfu")}
    lo, hi = ctx["lo"], ctx["hi"]
    inc = ph.split(ops, lo, hi, PHASES).inclusive
    assert got["krylov_ms_per_step"] == pytest.approx(inc["krylov_solve"] / 1e6)
    assert got["grad_ms_per_step"] == pytest.approx(inc["grad_build"] / 1e6)
    assert 0 < got["curvature_product_ms_per_step"] < got["krylov_ms_per_step"]
    b = int(cell.traffic["batch"] * cell.traffic["optimizer"]["hvp_batch_frac"])
    want = (100.0 * flops.bicgstab_products(iters) * flops.hvp(Job.matmuls(b))
            / (got["curvature_product_ms_per_step"] * 1e-3 * 197e12))
    assert got["curvature_product_mfu"] == pytest.approx(want)
    assert 0 < got["curvature_product_mfu"] <= 100
