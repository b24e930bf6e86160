"""The fused curvature product's engagement (``bench/metrics/
fused_hvp_share.py``): launches of the ``mlp_hvp`` kernel in the traced
window over the traced solves' operator applications, on synthetic ops and
on the recorded ``timit-b16k`` step, which predates the kernel."""
import gzip
import importlib.util
import os

import pytest

from bench import phases as ph
from bench import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B16K_STEP = os.path.join(BENCH, "tests", "fixtures",
                         "timit-b16k_one_step.xplane.pb.gz")
KERNEL_OP = ("jit(step)/krylov_solve/while/body/curvature_product/"
             "curvature_primal/jvp(jvp(mlp_hvp))/pallas_call")
XLA_OP = "jit(step)/krylov_solve/while/body/curvature_product/jvp(jvp())/dot_general"


def share_reader():
    spec = importlib.util.spec_from_file_location(
        "metric_fused_hvp_share",
        os.path.join(BENCH, "metrics", "fused_hvp_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def solve(n_kernel, n_xla=0, t0=0, module=ph.STEP_MODULE):
    """A solve's ops: ``n_kernel`` kernel launches and ``n_xla`` XLA
    products, 10 ns apart from ``t0``."""
    ops, t = [], t0
    for op_name in [KERNEL_OP] * n_kernel + [XLA_OP] * n_xla:
        ops.append(ph.Op(t, t + 5, module, op_name, "%op"))
        t += 10
    return ops


# 16 iterations a step: 1 + 2·16 = 33 operator applications
@pytest.mark.parametrize("n_kernel,n_xla,want", [
    (33, 0, 100.0),               # every product fused
    (11, 22, 100.0 / 3),          # a third of them
    (0, 33, 0.0),                 # none: the plain path
])
def test_share_of_the_products(n_kernel, n_xla, want):
    m = share_reader()
    ops = solve(n_kernel, n_xla)
    got = m.share(m.launches(ops, 0, 10_000), [{"cg_iters": 16.0}])
    assert got == pytest.approx(want)


def test_only_the_steps_launches_in_the_window_count():
    m = share_reader()
    ops = (solve(5, t0=0) + solve(7, t0=1000)
           + solve(3, t0=1100, module="jit_heldout_loss"))
    assert m.launches(ops, 0, 1000) == 5
    assert m.launches(ops, 1000, 2000) == 7
    assert m.share(12, [{"cg_iters": 2.0}, {"cg_iters": 0.5}]) == \
        pytest.approx(100.0 * 12 / (5 + 2))


def ctx_of(t, devices=(0,)):
    lo, hi = tr.window(t)
    return {"trace": t, "lo": lo, "hi": hi, "devices": list(devices),
            "chips": 1, "traced_steps": [{"cg_iters": 13.0}]}


def test_a_trace_without_tpu_operations_reads_nothing():
    t = tr.Trace({}, [("hf_step", 0, 10)])
    assert share_reader().read(ctx_of(t)) is None


def recorded(tmp_path, monkeypatch):
    d = tmp_path / ".bench_out" / "trace" / "timit-b16k"
    d.mkdir(parents=True)
    with gzip.open(B16K_STEP, "rb") as f:
        (d / "step.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(ph, "ROOT", str(tmp_path))
    return tr.load(str(d / "step.xplane.pb"))


def test_the_plain_products_read_zero(tmp_path, monkeypatch):
    """The recorded step ran every product through XLA: 0%, not nothing."""
    assert share_reader().read(ctx_of(recorded(tmp_path, monkeypatch))) == 0.0


def test_a_program_without_the_kernel_reads_nothing(tmp_path, monkeypatch):
    m = share_reader()
    monkeypatch.setattr(m, "program_has_kernel", lambda: False)
    assert m.read(ctx_of(recorded(tmp_path, monkeypatch))) is None
