"""The plain reference against the program, on the CPU at small widths:
the same loss and gradient from the same weights and inputs."""
import jax
import numpy as np
import pytest

from bench import generate, harness


def build(cfg_name, family, cfg_over, traffic):
    mod = harness.load_module(
        harness.os.path.join(harness.BENCH, "configs", cfg_name + ".py"),
        "ref_test_" + cfg_name.replace("-", "_"))
    cfg = dict(harness.load_json(harness.os.path.join(
        harness.BENCH, "configs", cfg_name + ".json")), **cfg_over)
    import importlib
    fam = importlib.import_module(f"bench.families.{family}")
    return fam.build(cfg, traffic, mod, 1)


def assert_close(a, b, rtol):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        scale = max(np.abs(y).max(), 1e-12)
        assert np.abs(x - y).max() <= rtol * scale


def test_timit_reference_is_the_program_network():
    traffic = {"generator": "timit_frames", "corpus_seed": 1, "train_frames": 3000, "heldout_frames": 100,
               "proto_scale": 0.25, "noise": 1.0, "batch": 256,
               "optimizer": {"name": "bicgstab", "hvp_batch_frac": 0.25}}
    job = build("timit-fig5", "mlp", {}, traffic)
    kd, kp = jax.random.split(generate.seed_key(3))
    data, params = job.make_data(kd), job.make_params(kp)
    batch = job.draw(data, np.int32(0))
    f, g = jax.value_and_grad(job.loss_fn)(params, batch)
    fr, gr = jax.value_and_grad(job.reference_loss)(params, batch)
    assert float(f) == pytest.approx(float(fr), rel=1e-6)
    assert_close(g, gr, 1e-5)
