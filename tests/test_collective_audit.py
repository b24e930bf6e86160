"""Collective-schedule audit: the jaxpr, the executed program, and
``KrylovResult.syncs`` must tell the same story.

Every all-reduce in ``core.distributed.data_parallel_hf_step`` goes through
``core.collectives.preduce`` (a tagged pmean), which makes the schedule
auditable at two levels:

  * STATIC — ``jaxpr_collective_counts`` walks the traced step and counts
    psum-family equations, split into unconditionally-executed ("top") vs
    inside-a-while-body ("while_body") regions. Pure data parallelism means
    the ONLY collectives are all-reduces (pmean binds one psum per reduced
    leaf): no all-gathers or all-to-alls of model state, for every
    solver × s-step × curvature combo.
  * EXECUTED — ``count_executed`` tallies each preduce tag once per actual
    execution (while_loop trips included), which must reconcile with the
    per-step metrics: ``loss`` reduces = 1 (f0) + one per line-search eval,
    ``grad_hvp`` reduces = gradient + initial-residual probe + the basis /
    per-iteration operator products, and ``metrics["krylov_syncs"]``
    (= ``KrylovResult.syncs``) + the line-search terms must equal both
    ``metrics["blocking_syncs"]`` and the §3 comm-model formula
    (``hf_sstep_syncs_per_iteration``) at the EXECUTED iteration counts.

The single-device mesh is deliberate: shard_map binds the same collective
primitives regardless of axis size, so the schedule audited here is the one
the 2-process harness executes (tests/test_multiproc.py runs the real
thing; benchmarks/fig5_scaling.py --executed cross-checks at N=2).
"""
import jax
import pytest

from repro.core import HFConfig, hf_init
from repro.core.collectives import count_executed, jaxpr_collective_counts
from repro.core.distributed import data_parallel_hf_step
from repro.data import classification_dataset
from repro.models import build_mlp

from benchmarks.comm_model import (hf_sstep_syncs_per_iteration,
                                   sstep_bootstrap)

K = 8  # with cg_tol=0 the CG-family solves run to truncation/max_iters

# solver × s-step × curvature grid. `static`: the audited (top, while_body)
# psum equation counts — a deterministic fingerprint of the schedule. pmean
# binds one psum per leaf, so a scalar reduce counts 1 and a reduce of the
# 4-leaf MLP's params/gradient/product counts 4. If a change here is
# INTENTIONAL (a reduce added/removed/moved), update the table and
# EXPERIMENTS.md §Perf pair I together.
COMBOS = {
    "hessian_cg_s1": dict(solver="hessian_cg", s=1, basis="monomial",
                          overlap=False, curv="linearize", static=(11, 6)),
    "hessian_cg_s2": dict(solver="hessian_cg", s=2, basis="monomial",
                          overlap=False, curv="linearize", static=(16, 16)),
    "hessian_cg_s2_overlap": dict(solver="hessian_cg", s=2, basis="monomial",
                                  overlap=True, curv="linearize",
                                  static=(16, 27)),
    "hessian_cg_s2_chunked": dict(solver="hessian_cg", s=2, basis="monomial",
                                  overlap=False, curv="chunked",
                                  static=(16, 13)),
    "gn_cg_s1": dict(solver="gn_cg", s=1, basis="monomial",
                     overlap=False, curv="linearize", static=(12, 5)),
    "gn_cg_s4_newton": dict(solver="gn_cg", s=4, basis="newton",
                            overlap=False, curv="linearize", static=(32, 21)),
    "bicgstab_s1": dict(solver="bicgstab", s=1, basis="monomial",
                        overlap=False, curv="linearize", static=(11, 11)),
    "bicgstab_s2_newton": dict(solver="bicgstab", s=2, basis="newton",
                               overlap=False, curv="linearize",
                               static=(56, 31)),
}


@pytest.fixture(scope="module")
def setup():
    model = build_mlp((16, 32, 4))
    params = model.init(jax.random.PRNGKey(1))
    data = classification_dataset(jax.random.PRNGKey(0), 16, 16, 4)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    return model, params, data, mesh


def _make_step(model, mesh, spec):
    cfg = HFConfig(solver=spec["solver"], max_cg_iters=K, cg_tol=0.0,
                   sstep_s=spec["s"], sstep_basis=spec["basis"],
                   overlap=spec["overlap"], curvature_mode=spec["curv"])
    kw = (dict(model_out_fn=model.logits_fn, out_loss_fn=model.out_loss_fn)
          if spec["solver"] == "gn_cg" else {})
    return cfg, data_parallel_hf_step(model.loss_fn, mesh, cfg, **kw)


@pytest.mark.parametrize("name", list(COMBOS))
def test_static_schedule_is_all_reduce_only(name, setup):
    model, params, data, mesh = setup
    spec = COMBOS[name]
    cfg, step = _make_step(model, mesh, spec)
    jaxpr = jax.make_jaxpr(step)(params, hf_init(params, cfg), data)
    counts = jaxpr_collective_counts(jaxpr.jaxpr)
    # Pure data parallelism: all-reduces only (pmean → psum), never an
    # all-gather/all-to-all of model state — in ANY region.
    prims = set(counts["top"]) | set(counts["while_body"])
    assert prims <= {"psum"}, (name, counts)
    assert sum(counts["top"].values()) > 0, name
    assert (counts["top"]["psum"], counts["while_body"]["psum"]) == \
        spec["static"], (name, counts)


def test_static_overlap_adds_only_loop_body_reduces(setup):
    """Overlap reorders/hides reduces and adds the speculative deep-half +
    paired line-search ones — all inside the solve/search loops; the
    unconditional top-level schedule is untouched."""
    base = COMBOS["hessian_cg_s2"]["static"]
    ov = COMBOS["hessian_cg_s2_overlap"]["static"]
    assert ov[0] == base[0]
    assert ov[1] > base[1]


@pytest.mark.parametrize("name", list(COMBOS))
def test_executed_counts_match_krylov_syncs_and_comm_model(name, setup):
    model, params, data, mesh = setup
    spec = COMBOS[name]
    cfg, step = _make_step(model, mesh, spec)
    with count_executed() as counts:
        p, s, m = jax.jit(step)(params, hf_init(params, cfg), data)
        jax.block_until_ready(p)
    executed = counts.per_device(len(jax.local_devices()))
    cg_iters, ls_evals = int(m["cg_iters"]), int(m["ls_evals"])
    krylov, blocking = int(m["krylov_syncs"]), int(m["blocking_syncs"])
    assert int(m["sstep_fallback"]) == 0, (name, executed, m)

    # Loss reduces: one f0 + one per line-search eval. Chunked curvature
    # adds one (its primal accumulation probes the pmean'd loss once).
    expect_loss = 1 + ls_evals + (1 if spec["curv"] == "chunked" else 0)
    assert executed["loss"] == expect_loss, (name, executed, ls_evals)
    # gn_cg's Gauss-Newton build probes the pmean'd output loss once.
    assert executed.get("out_loss", 0) == \
        (1 if spec["solver"] == "gn_cg" else 0), (name, executed)

    # Model-sized reduces: gradient + initial-residual probe (A x0) + the
    # operator products — per iteration for the standard solvers, per basis
    # chain level for s-step (cycles recovered from KrylovResult.syncs).
    family = "bicgstab" if spec["solver"] == "bicgstab" else "cg"
    if spec["s"] == 1:
        products = (2 if family == "bicgstab" else 1) * cg_iters
    else:
        s_eff = 2 * spec["s"] if spec["overlap"] else spec["s"]
        n_boot, covered = sstep_bootstrap(s_eff, family, spec["basis"])
        s_boot = covered // n_boot if n_boot else 0
        d = 2 * s_eff if family == "bicgstab" else s_eff
        d_boot = 2 * s_boot if family == "bicgstab" else s_boot
        cycles = krylov - n_boot  # one Gram reduction per executed cycle
        products = cycles * (2 * d - 1) + n_boot * max(2 * d_boot - 1, 0)
    assert executed["grad_hvp"] == 2 + products, (name, executed, m)

    # KrylovResult.syncs ↔ blocking_syncs ↔ §3 comm model, all at the
    # EXECUTED iteration/eval counts.
    if spec["overlap"]:
        assert blocking == krylov + (ls_evals + 1) // 2, (name, m)
    else:
        assert blocking == 1 + krylov + ls_evals, (name, m)
    assert blocking == hf_sstep_syncs_per_iteration(
        cg_iters, ls_evals, spec["s"], solver=family,
        basis=spec["basis"], overlap=spec["overlap"]), (name, m)
