"""Chip smoke test: drive the HF trainer's main path once on a TPU.

  python chip_smoke.py               # one chip: phases (a), (b), (c)
  python chip_smoke.py --four-chips  # four chips: data-parallel HF vs one chip

Phases, all in this one process (a chip belongs to one process at a time):

  (a) device check — JAX's first device must be a TPU, else exit non-zero;
  (b) the paper's TIMIT network (Fig. 5, 360-512-512-512-1973) at full
      width, built through ``optim.make_optimizer`` with Bi-CG-STAB on a
      seeded synthetic batch of 16384 (curvature batch 1/4 of it): a few
      outer steps with the pytree Krylov backend, then with the flat one.
      Losses must be finite and fall; the flat step's compiled program must
      hold the Pallas kernels (``tpu_custom_call``); the two backends' losses
      must agree (at init_damping 5, where the backends are known to agree);
  (c) the LM path through ``launch.train.train``: qwen2-1.5b at its smoke
      widths, flat backend, a few Gauss-Newton HF steps with the Pallas flash
      kernels (forward, dQ, dK/dV, JVP) and again with dense attention; the
      per-step losses must agree.

``--four-chips`` runs only the paper's setting across chips: the TIMIT
network's shard_map data-parallel HF step over a 4-device data mesh, and the
same step on one chip (the batch reordered so both take the same curvature
mini-batch), for a few outer steps; the losses must agree.

Compile and step seconds are printed as information. The last line of
standard output is the JSON result, printed only when every phase passed.
The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` if set,
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TIMIT_BATCH = 16384
HVP_FRAC = 0.25
OUTER_STEPS = 3
# Backend / topology parity is checked where Bi-CG-STAB is well damped:
# barely damped, it amplifies reduction-order noise into different
# trajectories (tests/test_krylov_backends.py).
PARITY_DAMPING = 5.0
# Relative tolerance on per-step losses for every comparison here.
LOSS_RTOL = 1e-3
LM_ARCH, LM_STEPS, LM_BATCH, LM_SEQ = "qwen2-1.5b", 2, 4, 256


def log(msg):
    print(f"chip_smoke: {msg}", flush=True)


def device_check(jax):
    """Phase (a): the first device must be a TPU; returns the result's
    device record."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform {dev.platform!r})")
    record = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {record}")
    return record


def check_losses(name, losses, ref=None, ref_name=None):
    """Finite, falling, and (given ``ref``) within LOSS_RTOL of it."""
    import numpy as np

    losses = np.asarray(losses, np.float64)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss {losses.tolist()}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall {losses.tolist()}")
    if ref is not None:
        ref = np.asarray(ref, np.float64)
        rel = np.max(np.abs(losses - ref) / np.abs(ref))
        log(f"{name} vs {ref_name}: max rel loss diff {rel:.3e} "
            f"(tolerance {LOSS_RTOL})")
        if not rel <= LOSS_RTOL:
            raise AssertionError(
                f"{name} {losses.tolist()} != {ref_name} {ref.tolist()}")


def timit_setup(jax):
    from repro.configs.paper_mlp import TIMIT_FIG5
    from repro.data import classification_dataset
    from repro.models import build_mlp

    model = build_mlp(TIMIT_FIG5)
    params = model.init(jax.random.PRNGKey(1))
    data = classification_dataset(jax.random.PRNGKey(0), TIMIT_BATCH,
                                  TIMIT_FIG5[0], TIMIT_FIG5[-1])
    return model, params, data


def run_hf(jax, name, model, params, data, *, mesh=None,
           krylov_backend="tree", expect_kernels=False):
    """OUTER_STEPS HF Bi-CG-STAB steps through make_optimizer; returns the
    per-step losses (the loss at the start of each step, then the last
    accepted one)."""
    from repro.configs import HFOptConfig
    from repro.launch import multiproc
    from repro.optim import make_optimizer

    opt = make_optimizer(
        HFOptConfig(name="bicgstab", hvp_batch_frac=HVP_FRAC,
                    init_damping=PARITY_DAMPING,
                    krylov_backend=krylov_backend),
        model.loss_fn, model_out_fn=model.logits_fn,
        out_loss_fn=model.out_loss_fn, mesh=mesh)
    state = opt.init(params)
    if mesh is not None:
        params = multiproc.replicate(params, mesh)
        state = multiproc.replicate(state, mesh)
        data = multiproc.shard_batch(data, mesh)
    t0 = time.perf_counter()
    compiled = jax.jit(opt.step).lower(params, state, data).compile()
    compile_s = time.perf_counter() - t0
    if expect_kernels and "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no Pallas kernel in the compiled step")
    losses, step_s = [], []
    for _ in range(OUTER_STEPS):
        t0 = time.perf_counter()
        params, state, m = compiled(params, state, data)
        jax.block_until_ready(params)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    losses.append(float(m["loss_new"]))
    log(f"{name}: compile {compile_s:.2f}s, step s {[round(t, 4) for t in step_s]}, "
        f"losses {losses}")
    return losses


def phase_timit(jax):
    """Phase (b): tree then flat Krylov backend on one chip."""
    model, params, data = timit_setup(jax)
    tree = run_hf(jax, "timit/tree", model, params, data)
    check_losses("timit/tree", tree)
    flat = run_hf(jax, "timit/flat", model, params, data,
                  krylov_backend="flat", expect_kernels=True)
    check_losses("timit/flat", flat, tree, "timit/tree")


def phase_lm(jax):
    """Phase (c): launch.train.train with and without the flash kernels.

    Both runs use f32 matmuls at full precision, so the comparison measures
    the kernels against dense attention, not the one-pass bf16 rounding of
    XLA's default f32 matmul on TPU."""
    from repro.launch.train import train

    runs = {}
    for flash in (True, False):
        name = f"{LM_ARCH}/{'flash' if flash else 'dense'}"
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            _, _, hist = train(
                LM_ARCH, smoke=True, solver="gn_cg", use_flash_attention=flash,
                krylov_backend="flat", steps=LM_STEPS, batch_size=LM_BATCH,
                seq_len=LM_SEQ, log_fn=lambda *a, **k: None)
        runs[flash] = [h["loss"] for h in hist] + [hist[-1]["loss_new"]]
        log(f"{name}: compile {hist[0]['compile_s']}s, step s "
            f"{[h['wall_s'] for h in hist]}, losses {runs[flash]}, "
            f"total {time.perf_counter() - t0:.1f}s")
    check_losses(f"{LM_ARCH}/flash", runs[True], runs[False],
                 f"{LM_ARCH}/dense")


def same_curvature_order(data, n_shards, frac):
    """Reorder a batch so that its leading ``frac`` is the union of the
    leading ``frac`` of each of ``n_shards`` equal shards — the rows the
    data-parallel step's per-shard curvature slices take."""
    import jax.numpy as jnp

    def reorder(x):
        shards = x.reshape(n_shards, -1, *x.shape[1:])
        q = int(shards.shape[1] * frac)
        return jnp.concatenate([shards[:, :q].reshape(-1, *x.shape[1:]),
                                shards[:, q:].reshape(-1, *x.shape[1:])])

    return {k: reorder(v) for k, v in data.items()}


def phase_four_chips(jax):
    """Data-parallel HF over four chips against the same step on one."""
    from repro.launch.mesh import make_data_mesh

    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, "
                         f"found {len(jax.devices())}")
    model, params, data = timit_setup(jax)
    mesh = make_data_mesh()
    dp = run_hf(jax, "timit/4-chip data-parallel", model, params, data,
                mesh=mesh)
    one = run_hf(jax, "timit/1-chip", model, params,
                 same_curvature_order(data, mesh.shape["data"], HVP_FRAC))
    check_losses("timit/1-chip", one)
    check_losses("timit/4-chip data-parallel", dp, one, "timit/1-chip")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel comparison")
    args = ap.parse_args(argv)

    import jax

    device = device_check(jax)
    from repro.launch.cache import enable_compile_cache

    log(f"compilation cache {enable_compile_cache()}")
    cache = {"hits": 0, "misses": 0}

    def count(event, **_):
        key = event.rsplit("/", 1)[-1].replace("cache_", "")
        if event.startswith("/jax/compilation_cache/") and key in cache:
            cache[key] += 1

    jax.monitoring.register_event_listener(count)
    if args.four_chips:
        phase_four_chips(jax)
    else:
        phase_timit(jax)
        phase_lm(jax)
    log(f"compilation cache {cache}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
