"""``correct`` on the CPU at a size a test run can hold: a sound run passes;
the control (the reference computed in bfloat16, in the program's place)
and each planted fault of ``bench/faults.py`` fail the cell's limits.

The runs skip the harness's look for a chip and drive everything else: the
same set-up, window, reference and comparison as a run on the chip.
"""
import os
import time

import jax
import jax.numpy as jnp
import pytest

from bench import check, faults, harness

TINY_MLP = {"layer_dims": [36, 64, 64, 37]}
TINY_FRAMES = {"train_frames": 20000, "heldout_frames": 1000, "batch": 512,
               "eval_every": 5, "trace_steps": 3, "warm_steps": 6}


@pytest.fixture(autouse=True)
def own_compile_cache(tmp_path):
    """A run turns JAX's persistent cache on for its process; keep that, and
    its settings, to this file's tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    old_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    cc.reset_cache()
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    if old_env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old_env
    cc.reset_cache()


def tiny_cell(name):
    cell = harness.Cell(name)
    cell.cfg = dict(cell.cfg, **TINY_MLP)
    cell.traffic = dict(cell.traffic, **TINY_FRAMES)
    return cell


def run(cell, fault=None, seconds=0.5, trace=False):
    device = harness.device_record(jax, cell.chips, {}, require_tpu=False)
    return harness.run(jax, cell, 3, seconds, trace, device,
                       {"flops_per_s": 1e12}, time.perf_counter(), fault=fault)


def test_sound_run_is_correct():
    out = run(tiny_cell("timit-b16k"))
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in harness.Cell(
        "timit-b16k").end_to_end}
    assert set(out["checks"]) == set(harness.Cell("timit-b16k").limits["limits"]) | {
        "compiles_in_window"}


def test_traced_run_reads_the_per_layer_metrics():
    out = run(tiny_cell("timit-b16k"), trace=True)
    assert out["correct"] is True
    assert {"mfu", "device_idle_share", "krylov_iters_per_step"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert len(out["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_fault_is_not_correct(fault):
    out = run(tiny_cell("timit-b16k"), fault=faults.FAULTS[fault])
    assert out["correct"] is False


def test_control_fails_a_limit():
    cell = tiny_cell("timit-b164k")
    job = harness.build_job(cell)
    k_params, drv, warm, _ = harness.start(jax, job, cell, 5)
    batches = harness.compared_batches(jax, drv, warm)
    del drv
    params0 = jax.device_get(job.make_params(k_params))
    ref = check.reference(jax, job, cell, params0, warm, batches)
    ctl = check.reference(jax, job, cell, params0, warm, batches,
                          dtype=jnp.bfloat16, precision="default")
    numbers = check.compare(jax, ctl, ref, params0, warm)
    limits = cell.limits["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers


def test_trace_without_device_operations_is_refused_on_a_tpu():
    """The CPU's trace holds no TPU plane: read as a TPU's, it must raise and
    not report a device that was idle all through."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(RuntimeError, match="no device operation"):
        harness.run(jax, tiny_cell("timit-b16k"), 3, 0.3, True, device,
                    {"flops_per_s": 1e12}, time.perf_counter())
