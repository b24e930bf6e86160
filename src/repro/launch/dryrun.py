import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes with ShapeDtypeStruct inputs (no allocation), then
record memory analysis, cost analysis and the collective schedule for the
roofline report.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count on first init); this module is the only place it is set.
(No ``from __future__`` here for the same reason — nothing may precede the
env-var lines.)

Train shapes lower the paper's HF step (Alg. 2: grad all-reduce + Krylov
loop with per-iteration HVP all-reduce + Armijo loop) — the compiled HLO *is*
the paper's communication schedule. ``--solver sgd`` lowers the SGD baseline
instead (for the paper's collectives-per-epoch comparison). Decode shapes
lower ``serve_step`` (one token against a seq_len KV/state cache); prefill
shapes lower the full-sequence cache-building forward pass.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, INPUT_SHAPES, get_config
from ..core import HFConfig, HFState, hf_init, hf_step
from ..data.synthetic import batch_spec
from ..models import build_model
from ..roofline import (
    collective_bytes_from_hlo,
    cost_summary,
    model_flops,
    roofline_terms,
)
from .mesh import batch_axes_if_divisible, make_production_mesh
from .sharding import batch_specs, cache_specs, param_specs, to_shardings

from jax.sharding import NamedSharding, PartitionSpec as P

# long_500k needs sub-quadratic attention: dense/vlm archs run a
# sliding-window variant (window below). Whisper's decoder has 448 learned
# positions, so every shape longer than that is skipped for it.
LONG_CONTEXT_WINDOW = 8192
# sLSTM recurs sequentially over time: a 524288-step lax.scan is lowerable
# but not a deployable prefill; xlstm long-context decode still exercises it
# (single step), which is the case that matters.


def input_specs(cfg, shape):
    """ShapeDtypeStruct stand-ins for every model input of this workload."""
    return batch_spec(cfg, shape.global_batch, shape.seq_len, shape.kind)


def adapt_config(arch_id: str, shape_name: str, ce_chunk: int = 0,
                 shard_hints: bool = False):
    cfg = get_config(arch_id)
    if shape_name == "long_500k" and cfg.family in ("dense", "vlm"):
        cfg = cfg.replace(sliding_window=LONG_CONTEXT_WINDOW)
    if ce_chunk:
        cfg = cfg.replace(ce_chunk=ce_chunk)
    if shard_hints:
        cfg = cfg.replace(shard_hints=True)
    return cfg


def make_mesh_from(spec: str):
    """"16x16" -> ("data","model") mesh; "2x16x16" -> ("pod","data","model")."""
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    return jax.make_mesh(dims, axes)


def _hf_state_specs(pspecs):
    return HFState(lam=P(), prev_delta=pspecs, use_gn=P(), step=P())


def build_lowering(arch_id: str, shape_name: str, mesh, *, solver="bicgstab",
                   fsdp=True, remat=True, max_cg_iters=8, ce_chunk=0,
                   shard_cache_hd=False, shard_hints=False):
    cfg = adapt_config(arch_id, shape_name, ce_chunk, shard_hints)
    shape = INPUT_SHAPES[shape_name]
    model = build_model(cfg, remat=remat and shape.kind == "train")

    p_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_specs(p_struct, cfg, mesh, fsdp=fsdp)
    psh = to_shardings(pspecs, mesh)

    if shape.kind == "train":
        b_struct = input_specs(cfg, shape)
        bsh = to_shardings(batch_specs(b_struct, mesh), mesh)
        if solver == "sgd":
            from ..optim import sgd

            opt = sgd(0.1)

            def step(p, b):
                return opt.step(model.loss_fn, p, (), b)[::2]

            fn = jax.jit(step, in_shardings=(psh, bsh), out_shardings=(psh, None))
            return fn, (p_struct, b_struct), cfg, shape

        hf_cfg = HFConfig(solver=solver, max_cg_iters=max_cg_iters, max_backtracks=6)
        s_struct = jax.eval_shape(lambda p: hf_init(p, hf_cfg), p_struct)
        ssh = to_shardings(_hf_state_specs(pspecs), mesh)

        def hvp_slice(b):
            return jax.tree_util.tree_map(lambda x: x[: max(x.shape[0] // 4, 1)], b)

        def step(p, s, b):
            return hf_step(model.loss_fn, p, s, b, hvp_slice(b), hf_cfg)

        fn = jax.jit(
            step, in_shardings=(psh, ssh, bsh), out_shardings=(psh, ssh, None),
            donate_argnums=(0, 1),
        )
        return fn, (p_struct, s_struct, b_struct), cfg, shape

    if shape.kind == "prefill":
        b_struct = input_specs(cfg, shape)
        bsh = to_shardings(batch_specs(b_struct, mesh), mesh)
        c_struct = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        csh = to_shardings(
            cache_specs(c_struct, cfg, mesh, shape.global_batch, shard_hd=shard_cache_hd),
            mesh,
        )

        def step(p, b):
            return model.prefill(p, b, shape.seq_len)

        fn = jax.jit(step, in_shardings=(psh, bsh), out_shardings=(None, csh))
        return fn, (p_struct, b_struct), cfg, shape

    # decode: one new token with a seq_len cache
    c_struct = jax.eval_shape(lambda: model.init_cache(shape.global_batch, shape.seq_len))
    csh = to_shardings(
        cache_specs(c_struct, cfg, mesh, shape.global_batch, shard_hd=shard_cache_hd),
        mesh,
    )
    tok_struct = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_axes = batch_axes_if_divisible(mesh, shape.global_batch)
    tok_sh = NamedSharding(mesh, P(tok_axes) if tok_axes else P())
    t_struct = jax.ShapeDtypeStruct((), jnp.int32)

    def step(p, tok, t, cache):
        return model.decode_step(p, tok, t, cache)

    fn = jax.jit(
        step,
        in_shardings=(psh, tok_sh, NamedSharding(mesh, P()), csh),
        out_shardings=(None, csh),
        donate_argnums=(3,),
    )
    return fn, (p_struct, tok_struct, t_struct, c_struct), cfg, shape


def run_one(arch_id: str, shape_name: str, *, multi_pod: bool, solver="bicgstab",
            fsdp=True, remat=True, max_cg_iters=8, keep_hlo=False,
            mesh_spec=None, ce_chunk=0, shard_cache_hd=False,
            shard_hints=False) -> dict:
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": mesh_spec or ("2x16x16" if multi_pod else "16x16"),
        "solver": solver, "fsdp": fsdp, "remat": remat,
        "ce_chunk": ce_chunk, "shard_cache_hd": shard_cache_hd,
        "shard_hints": shard_hints,
    }
    cap = get_config(arch_id).max_target_positions
    if cap is not None and INPUT_SHAPES[shape_name].seq_len > cap:
        rec["status"] = "skipped"
        rec["reason"] = f"the decoder has {cap} positions"
        return rec
    t0 = time.time()
    try:
        mesh = (make_mesh_from(mesh_spec) if mesh_spec
                else make_production_mesh(multi_pod=multi_pod))
        n_chips = mesh.size
        fn, structs, cfg, shape = build_lowering(
            arch_id, shape_name, mesh, solver=solver, fsdp=fsdp, remat=remat,
            max_cg_iters=max_cg_iters, ce_chunk=ce_chunk,
            shard_cache_hd=shard_cache_hd, shard_hints=shard_hints,
        )
        with mesh:
            lowered = fn.lower(*structs)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(ma, k))
                for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                )
                if hasattr(ma, k)
            }
            arg = rec["memory"].get("argument_size_in_bytes", 0)
            tmp = rec["memory"].get("temp_size_in_bytes", 0)
            rec["memory"]["per_device_total_gib"] = round((arg + tmp) / 2**30, 3)
        except Exception as e:  # CPU backend may not support it
            rec["memory"] = {"error": str(e)}
        cost = cost_summary(compiled.cost_analysis())
        hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo)
        rec["cost"] = cost
        rec["collectives"] = coll
        terms = roofline_terms(
            cost.get("flops", 0.0), cost.get("bytes_accessed", 0.0),
            coll["total"], n_chips,
        )
        rec["roofline"] = terms
        mf = model_flops(cfg, shape)
        rec["model_flops_global"] = mf
        hlo_flops_global = cost.get("flops", 0.0) * n_chips
        rec["useful_flops_ratio"] = (
            round(mf / hlo_flops_global, 4) if hlo_flops_global else None
        )
        rec["status"] = "ok"
        if keep_hlo:
            rec["hlo_path"] = _dump_hlo(rec, hlo)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def _dump_hlo(rec, hlo) -> str:
    os.makedirs("experiments/hlo", exist_ok=True)
    path = f"experiments/hlo/{rec['arch']}_{rec['shape']}_{rec['mesh']}_{rec['solver']}.hlo"
    with open(path, "w") as f:
        f.write(hlo)
    return path


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true", help="all arch x shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--solver", default="bicgstab",
                    choices=["bicgstab", "gn_cg", "hessian_cg", "hybrid_cg", "sgd"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--max-cg-iters", type=int, default=8)
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help='override mesh, e.g. "32x8" (data x model, 256 chips)')
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="chunked cross-entropy vocab chunk (0=off)")
    ap.add_argument("--shard-cache-hd", action="store_true",
                    help="shard decode-cache head_dim on model when kv-heads cannot shard")
    ap.add_argument("--shard-hints", action="store_true",
                    help="explicit sharding constraints on MoE dispatch intermediates")
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.all or args.arch is None else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_one(
                    arch, shape, multi_pod=mp, solver=args.solver,
                    fsdp=not args.no_fsdp, remat=not args.no_remat,
                    max_cg_iters=args.max_cg_iters, keep_hlo=args.keep_hlo,
                    mesh_spec=args.mesh, ce_chunk=args.ce_chunk,
                    shard_cache_hd=args.shard_cache_hd,
                    shard_hints=args.shard_hints,
                )
                mesh_tag = args.mesh or ("2pod" if mp else "1pod")
                suffix = f"_{args.tag}" if args.tag else ""
                path = os.path.join(
                    args.out, f"{arch}_{shape}_{mesh_tag}_{args.solver}{suffix}.json")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1, default=str)
                status = rec["status"]
                extra = (
                    f"bottleneck={rec['roofline']['bottleneck']}"
                    if status == "ok" else rec.get("reason", rec.get("error", ""))[:120]
                )
                print(f"[{status:7s}] {arch:22s} {shape:12s} {mesh_tag} "
                      f"{rec.get('total_s', 0):7.1f}s  {extra}", flush=True)


if __name__ == "__main__":
    main()
