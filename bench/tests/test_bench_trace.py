"""The reduction from a profiler trace to per-layer metrics: interval
arithmetic by hand, the readers on a small synthetic trace, and the reducer
on a trace recorded on a TPU v5e."""
import gzip
import importlib.util
import os

import pytest

from bench import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# One traced step of the harness on a TPU v5e: the TIMIT cell's traffic at
# a tiny size (network 36-64-64-37, batch 512), its held-out loss after the
# step.
FIXTURE = os.path.join(BENCH, "tests", "fixtures", "tpu_v5e_one_step.xplane.pb.gz")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_union_clip_intersect():
    ivs = [("a", 0, 10), ("b", 5, 12), ("c", 20, 30), ("d", 21, 22)]
    assert tr.union(ivs) == [(0, 12), (20, 30)]
    assert tr.clip(tr.union(ivs), 8, 25) == [(8, 12), (20, 25)]
    assert tr.covered([(0, 12), (20, 30)]) == 22


def synthetic():
    host = [("hf_step", 0, 100), ("batch_draw", 0, 10), ("dispatch", 10, 20),
            ("metric_pull", 20, 100)]
    ops = {0: [("fusion.1", 15, 40), ("all-reduce.2", 40, 50),
               ("fusion.3", 45, 60), ("while.4", 70, 90)]}
    return tr.Trace(ops, host)


def test_busy_gaps_and_tops():
    t = synthetic()
    assert tr.window(t) == (0, 100)
    assert tr.busy_ns(t, 0, 0, 100) == 45 + 20
    gaps = tr.gaps(t, 0, 0, 100)
    assert gaps == [("batch_draw", 0, 15), ("metric_pull", 60, 70),
                    ("metric_pull", 90, 100)]
    assert tr.top_gaps(t, 0, 0, 100)[0] == ["batch_draw", pytest.approx(15e-9)]
    assert tr.top_ops(t, 0, 0, 100, n=1) == [["fusion.1", pytest.approx(25e-9)]]


def test_nested_operations_count_their_own_time():
    ops = [("%while.1 = (f32[2]{0}, s32[]) while(%t), body=%b", 0, 100),
           ("%fusion.2 = f32[8,4]{1,0:T(8,128)} fusion(%p), kind=kLoop", 10, 30),
           ("%fusion.2 = f32[8,4]{1,0:T(8,128)} fusion(%p), kind=kLoop", 50, 60),
           ("%copy.3 = f32[8]{0} copy(%q)", 120, 130)]
    t = tr.Trace({0: ops}, [])
    assert tr.top_ops(t, 0, 0, 200) == [
        ["%while.1 while tuple", pytest.approx(70e-9)],
        ["%fusion.2 fusion f32[8,4]", pytest.approx(30e-9)],
        ["%copy.3 copy f32[8]", pytest.approx(10e-9)]]
    assert tr.busy_ns(t, 0, 0, 200) == 110


def test_readers_on_synthetic_trace():
    t = synthetic()
    ctx = {"trace": t, "lo": 0, "hi": 100, "devices": [0], "chips": 2,
           "traced_steps": [{}]}
    assert reader("device_idle_share").read(ctx) == pytest.approx(35.0)


def test_mfu_counts_the_traced_steps():
    from bench import flops

    class Job:
        @staticmethod
        def matmuls(rows):
            return [(2.0 * rows * 3 * 4, 1), (2.0 * rows * 4 * 2, 2)]

    steps = [{"cg_iters": 3.0, "ls_evals": 1.0}, {"cg_iters": 16.0, "ls_evals": 2.0}]
    ctx = {"job": Job, "traffic": {"batch": 8, "optimizer": {"hvp_batch_frac": 0.25}},
           "traced_steps": steps, "lo": 0, "hi": 2_000_000_000, "chips": 1,
           "peak": {"flops_per_s": 1e3}}
    want = sum(flops.hf_step(Job.matmuls(8), Job.matmuls(2), m["cg_iters"],
                             m["ls_evals"]) for m in steps)
    assert reader("mfu").read(ctx) == pytest.approx(100.0 * want / (2.0 * 1e3))
    assert reader("krylov_iters_per_step").read({"steps": steps}) == 9.5


def test_reducer_on_a_trace_recorded_on_the_chip(tmp_path):
    path = tmp_path / "one_step.xplane.pb"
    with open(FIXTURE, "rb") as f:
        path.write_bytes(gzip.decompress(f.read()))
    t = tr.load(str(path))
    assert list(t.device_ops) == [0]
    names = {iv[0] for iv in t.host_spans}
    assert {"hf_step", "batch_draw", "dispatch", "metric_pull",
            "heldout_eval"} <= names
    lo, hi = tr.window(t)
    busy = tr.busy_ns(t, 0, lo, hi)
    assert 0 < busy < hi - lo
    own = tr.self_ns(t.device_ops[0], lo, hi)
    assert sum(own.values()) == busy
    ops = tr.top_ops(t, 0, lo, hi)
    assert 0 < len(ops) <= 10 and all(len(name) < 120 for name, _ in ops)
    assert any(" while " in name for name, _ in tr.top_ops(t, 0, lo, hi, n=100))
    gaps = tr.top_gaps(t, 0, lo, hi)
    assert {g[0] for g in gaps} <= set(tr.HOST_SPANS) | {"host"}
    idle = reader("device_idle_share").read(
        {"trace": t, "lo": lo, "hi": hi, "devices": [0]})
    assert idle == pytest.approx(100.0 * (1 - busy / (hi - lo)))
