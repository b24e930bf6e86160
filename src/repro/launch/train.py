"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --solver bicgstab --steps 20

Runs the distributed HF optimizer (or a first-order baseline) on synthetic
LM data, with checkpointing and metric logging. ``--smoke`` selects the
reduced config (CPU-runnable); without it the full config is used (TPU).

Whenever JAX sees more than one device — the chips of one TPU host driven
by this one process, or the processes of a ``--num-processes N`` run — the
step is the explicit shard_map data-parallel HF step (core/distributed.py)
over a "data" mesh of all of them. ``--num-processes N`` (N > 1)
re-launches this same command as N coordinated CPU processes
(launch/multiproc.py, gloo collectives, one device each): the harness that
makes the collectives cross a real process boundary. ``--overlap`` turns
on the overlapped-collective schedule (HFConfig.overlap: double-buffered
s-step cycles, hidden gradient reduce, paired line search).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from ..checkpoint import config_fingerprint, restore_latest_valid, save_checkpoint
from ..configs import ARCH_IDS, HFOptConfig, get_config, get_smoke_config
from ..core import collectives as collectives_mod
from ..data import lm_batch
from ..models import build_model
from ..obs import telemetry as telemetry_mod
from ..obs import trace as trace_mod
from ..optim import make_optimizer
from . import faults as faults_mod
from . import multiproc
from .cache import enable_compile_cache
from .mesh import make_data_mesh


def train(
    arch: str,
    *,
    smoke: bool = True,
    solver: str = "bicgstab",
    use_flash_attention: bool = False,
    steps: int = 20,
    batch_size: int = 8,
    seq_len: int = 64,
    lr: float = 0.1,
    hvp_batch_frac: float = 0.25,
    max_cg_iters: int = 8,
    precondition: bool = False,
    krylov_backend: str = "tree",
    curvature_mode: str = "linearize",
    curvature_chunk_size: int = 0,
    sstep: int = 1,
    sstep_solver: str = "auto",
    sstep_basis: str = "monomial",
    overlap: bool = False,
    nc_mode: str = "truncate",
    strict_descent: bool = False,
    ckpt_dir: str | None = None,
    ckpt_every: int = 0,
    telemetry_dir: str | None = None,
    watchdog_s: float = 0.0,
    log_fn=print,
):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if use_flash_attention:
        cfg = cfg.replace(use_flash_attention=True)
    model = build_model(cfg)
    opt_cfg = HFOptConfig(
        name=solver, lr=lr, hvp_batch_frac=hvp_batch_frac,
        max_cg_iters=max_cg_iters, precondition=precondition,
        krylov_backend=krylov_backend,
        curvature_mode=curvature_mode,
        curvature_chunk_size=curvature_chunk_size,
        sstep_s=sstep, sstep_solver=sstep_solver, sstep_basis=sstep_basis,
        overlap=overlap, nc_mode=nc_mode, strict_descent=strict_descent,
    )
    mesh = None
    if len(jax.devices()) > 1:
        # Data parallelism over every global device. Every process builds
        # the SAME global mesh and the same batch/params from the same PRNG;
        # only the device_put placement differs per process.
        mesh = make_data_mesh()
        n_shards = mesh.shape["data"]
        if batch_size % n_shards != 0:
            raise ValueError(
                f"batch_size {batch_size} not divisible by data-mesh size {n_shards}"
            )
        if not multiproc.is_primary():
            log_fn = lambda *a, **k: None  # noqa: E731  (primary-only logging)
    opt = make_optimizer(
        opt_cfg, model.loss_fn, model_out_fn=model.logits_fn,
        out_loss_fn=model.out_loss_fn, mesh=mesh,
    )

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    state = opt.init(params)
    # The manifest fingerprint covers everything that determines the step
    # program + batch stream; restore refuses checkpoints from any other
    # configuration instead of trusting the step number (satellite 1).
    nproc = jax.process_count()
    fingerprint = config_fingerprint(dict(
        arch=arch, smoke=smoke, opt=opt_cfg,
        batch_size=batch_size, seq_len=seq_len))
    start = 0
    if ckpt_dir:
        restored = restore_latest_valid(
            ckpt_dir, params, state,
            expect_fingerprint=fingerprint, expect_processes=nproc)
        if restored is not None:
            params, state, meta, ck_step = restored
            start = meta["step"]
            log_fn(f"restored checkpoint at step {start}")
    if mesh is not None:
        params = multiproc.replicate(params, mesh)
        state = multiproc.replicate(state, mesh)

    # Telemetry (repro.obs): per-process JSONL sink. The sink must be
    # installed while the step function is TRACED — the in-jit hooks are
    # trace-time, so a program compiled outside the install context never
    # fires a callback (zero-cost when --telemetry-dir is absent).
    sink = None
    if telemetry_dir:
        sink = telemetry_mod.Telemetry(
            telemetry_dir, process_index=jax.process_index(),
            meta=dict(kind="train", arch=arch, solver=solver, steps=steps,
                      batch_size=batch_size, seq_len=seq_len, sstep=sstep,
                      overlap=overlap, processes=jax.process_count(),
                      attempt=multiproc.restart_attempt()),
        )
        # SIGTERM (supervisor teardown) / SIGINT / normal exit all flush
        # the sink — a killed worker's partial event file stays parseable.
        telemetry_mod.register_crash_flush(sink)

    plan = faults_mod.FaultPlan.from_env(jax.process_index(), telemetry=sink)
    if plan.active():
        log_fn(f"fault plan armed: "
               f"{'; '.join(f.spec() for f in plan.faults)}")

    step_fn = jax.jit(opt.step)
    compiled = None
    history = []
    for i in range(start, steps):
        multiproc.heartbeat(i)
        plan.on_step_begin(i)
        batch = lm_batch(jax.random.fold_in(key, 1000 + i), cfg, batch_size, seq_len)
        batch = plan.poison_batch(i, batch)
        if mesh is not None:
            batch = multiproc.shard_batch(batch, mesh)
        if compiled is None:
            # AOT split: trace under the telemetry install context (hooks are
            # trace-time), then time XLA compilation separately so step 0's
            # wall_s measures the step, not the compile. The collective
            # watchdog is a trace-time install too; its monitor thread
            # outlives the context (daemon — dies with the process).
            install = (telemetry_mod.install(sink) if sink is not None
                       else contextlib.nullcontext())
            watchdog = (collectives_mod.collective_watchdog(watchdog_s)
                        if watchdog_s > 0 else contextlib.nullcontext())
            compile_span = (sink.span("compile", step=i) if sink is not None
                            else contextlib.nullcontext())
            tc = time.time()
            with compile_span:
                with install, watchdog:
                    lowered = step_fn.lower(params, state, batch)
                compiled = lowered.compile()
            compile_s = round(time.time() - tc, 3)
            multiproc.heartbeat(i)  # compile can dwarf hang_timeout_s steps
        host_span = (sink.span("host_step", step=i) if sink is not None
                     else contextlib.nullcontext())
        with host_span:
            t0 = time.time()
            params, state, metrics = compiled(params, state, batch)
            # One sync point + one host transfer for the whole metrics dict
            # (the old per-key float() forced a device round-trip per entry).
            jax.block_until_ready((params, state, metrics))
            wall_s = round(time.time() - t0, 3)
            metrics = {k: float(v) for k, v in jax.device_get(metrics).items()}
        metrics["step"] = i
        metrics["wall_s"] = wall_s
        if i == start:
            metrics["compile_s"] = compile_s
        history.append(metrics)
        if sink is not None:
            sink.counter("loss", metrics["loss"])
        log_fn(
            f"step {i:4d} loss {metrics['loss']:.4f} |g| {metrics['grad_norm']:.3f}"
            + (f" λ {metrics['lambda']:.3g} α {metrics['alpha']:.2f} cg {metrics['cg_iters']:.0f}"
               if "lambda" in metrics else "")
        )
        if (ckpt_dir and ckpt_every and (i + 1) % ckpt_every == 0
                and (mesh is None or multiproc.is_primary())):
            save_checkpoint(ckpt_dir, i + 1, params, state,
                            fingerprint=fingerprint, processes=nproc)
            plan.corrupt_checkpoint(i + 1, ckpt_dir)
    if sink is not None:
        sink.close()
        if mesh is not None and jax.process_count() > 1:
            # Every process must have flushed its events file before the
            # primary merges; the barrier also keeps non-primaries alive
            # until the merge can read their output.
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("telemetry_flush")
        if mesh is None or multiproc.is_primary():
            out = trace_mod.merge_dir(telemetry_dir)
            log_fn(f"telemetry: merged trace at {out}")
    return params, state, history


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--solver", default="bicgstab",
                    choices=["sgd", "momentum", "adam", "gn_cg", "hessian_cg",
                             "hybrid_cg", "bicgstab"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--max-cg-iters", type=int, default=8)
    ap.add_argument("--flash-attention", action="store_true",
                    help="route attention through the differentiable Pallas "
                         "flash kernels (training + prefill; interpret mode "
                         "off-TPU — see EXPERIMENTS.md §Perf pair F)")
    ap.add_argument("--precondition", action="store_true",
                    help="Jacobi preconditioning (PCG / preconditioned Bi-CG-STAB)")
    ap.add_argument("--krylov-backend", default="tree", choices=["tree", "flat"],
                    help="Krylov vector backend: sharding-preserving pytrees "
                         "or flat buffers through the fused Pallas kernels")
    ap.add_argument("--curvature-mode", default="linearize",
                    choices=["naive", "linearize", "chunked"],
                    help="curvature engine: rebuild-per-call, linearize-once, "
                         "or chunked microbatch accumulation (flat memory)")
    ap.add_argument("--curvature-chunk-size", type=int, default=0,
                    help="chunked mode: examples per microbatch "
                         "(<=0 = whole curvature batch in one chunk)")
    ap.add_argument("--sstep", type=int, default=1,
                    help="s-step (communication-avoiding) Krylov solve: batch "
                         "the dots of S iterations into one Gram reduction "
                         "(<=1 = standard per-iteration recurrence)")
    ap.add_argument("--sstep-solver", default="auto",
                    choices=["auto", "cg", "bicgstab"],
                    help="s-step recurrence (auto derives it from --solver)")
    ap.add_argument("--sstep-basis", default="monomial",
                    choices=["monomial", "newton", "chebyshev"],
                    help="s-step chain polynomial: monomial power chains "
                         "(f32-safe to s~4 CG / s~2 Bi-CG-STAB) or the "
                         "Ritz-parameterized Newton/Chebyshev bases that "
                         "double usable s (free estimates from the cycle "
                         "Gram; falls back monomial -> standard on guard "
                         "failure)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped-collective schedule: double-buffered "
                         "s-step cycles (two cycles per Gram reduce), the "
                         "gradient all-reduce hidden behind the curvature "
                         "build, and paired speculative line-search trials "
                         "(reports metrics['blocking_syncs'])")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="spawn N coordinated processes (jax.distributed, "
                         "gloo CPU collectives, 1 device each) and run the "
                         "explicit shard_map data-parallel step over an "
                         "N-way data mesh; a CPU harness — on a TPU host "
                         "one process drives every chip, see "
                         "launch/multiproc.py")
    ap.add_argument("--nc-mode", default="truncate",
                    choices=["truncate", "escape"],
                    help="negative-curvature policy: 'truncate' (passive "
                         "φ-best competition at the solution's norm scale) "
                         "or 'escape' (saddle-free |λ_min|-scaled escape "
                         "step along the NC direction — the λ estimate is "
                         "threaded through KrylovResult.nc_lambda, "
                         "Ritz-refined on the s-step paths)")
    ap.add_argument("--strict-descent", action="store_true",
                    help="divergence sentinel also rejects steps whose "
                         "accepted line-search loss INCREASES (non-finite "
                         "updates are always rejected); rejected steps "
                         "keep params, boost λ, and report "
                         "metrics['step_rejected']")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="supervise the multi-process run: on a worker "
                         "death/hang, tear down the survivors and relaunch "
                         "everyone (resuming from the last valid "
                         "checkpoint) up to N times with exponential "
                         "backoff; 0 = unsupervised spawn")
    ap.add_argument("--hang-timeout", type=float, default=0.0,
                    help="supervisor liveness: restart when no worker "
                         "heartbeat for this many seconds (must cover "
                         "rendezvous + compile + one step); 0 = exit-code "
                         "detection only")
    ap.add_argument("--watchdog-s", type=float, default=0.0,
                    help="per-worker collective watchdog: a collective "
                         "blocked longer than this (peer presumed dead) "
                         "hard-exits the worker with code "
                         f"{multiproc.EXIT_WATCHDOG} so the supervisor "
                         "restarts immediately instead of waiting out "
                         "--hang-timeout; 0 = off")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--history-out", default=None)
    ap.add_argument("--telemetry-dir", default=None,
                    help="write per-process telemetry (events-p{N}.jsonl: "
                         "phase spans, executed-collective begin/end times, "
                         "Krylov solve summaries) and, on the primary at "
                         "exit, the merged Chrome/Perfetto trace.json; "
                         "omit for zero-cost (no callbacks compiled in). "
                         "Inspect with python -m repro.obs.report DIR")
    args = ap.parse_args()

    if args.num_processes > 1 and not multiproc.active():
        if args.max_restarts > 0:
            restarts = multiproc.spawn_supervised(
                args.num_processes, "repro.launch.train", sys.argv[1:],
                max_restarts=args.max_restarts,
                hang_timeout_s=args.hang_timeout or None,
            )
            print(f"[supervisor] run completed after {restarts} restart(s)",
                  file=sys.stderr)
        else:
            multiproc.spawn(args.num_processes, "repro.launch.train",
                            sys.argv[1:])
        return
    multiproc.initialize_from_env()

    _, _, history = train(
        args.arch, smoke=args.smoke, solver=args.solver, steps=args.steps,
        use_flash_attention=args.flash_attention,
        batch_size=args.batch_size, seq_len=args.seq_len, lr=args.lr,
        max_cg_iters=args.max_cg_iters, precondition=args.precondition,
        krylov_backend=args.krylov_backend,
        curvature_mode=args.curvature_mode,
        curvature_chunk_size=args.curvature_chunk_size,
        sstep=args.sstep, sstep_solver=args.sstep_solver,
        sstep_basis=args.sstep_basis,
        overlap=args.overlap,
        nc_mode=args.nc_mode,
        strict_descent=args.strict_descent,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        telemetry_dir=args.telemetry_dir,
        watchdog_s=args.watchdog_s,
    )
    if args.history_out and (not multiproc.active() or multiproc.is_primary()):
        os.makedirs(os.path.dirname(args.history_out) or ".", exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)


if __name__ == "__main__":
    main()
