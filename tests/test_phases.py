"""The step's phase scopes (repro.obs.telemetry.phase): every operation of
the compiled HF step is named by its phase, the loops and the curvature
products where they belong; the names survive the persistent compilation
cache; a telemetry host span lands in the profiler's trace."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import HFOptConfig
from repro.models import build_mlp
from repro.obs import telemetry
from repro.optim import make_optimizer

# instruction name, opcode and op_name of each line of a compiled module's text
INSTR = re.compile(r'^\s*(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(.*?'
                   r'metadata=\{[^}]*op_name="([^"]*)"', re.M)


def phases_of(op_name):
    return [t for t in re.findall(r"[A-Za-z_]\w*", op_name)
            if t in telemetry.PHASES]


def timit_step(solver="bicgstab", **kw):
    """The TIMIT network's HF step (360-512-512-512-1973 tanh) at small
    widths, as the benchmark's MLP cells build it."""
    model = build_mlp((36, 64, 64, 37), "tanh")
    opt = make_optimizer(HFOptConfig(name=solver, hvp_batch_frac=0.25, **kw),
                         model.loss_fn, model_out_fn=model.logits_fn,
                         out_loss_fn=model.out_loss_fn)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"x": jnp.zeros((256, 36)), "y": jnp.zeros((256,), jnp.int32)}
    return opt, params, batch


@pytest.fixture(scope="module")
def compiled_text():
    opt, params, batch = timit_step()
    c = jax.jit(opt.step).lower(params, opt.init(params), batch).compile()
    return c.as_text()


def instructions(text):
    """A compiled module's instruction lines, without their metadata."""
    return [re.sub(r",? (metadata=\{[^}]*\}|stack_frame_id=\d+)", "", line)
            for line in text.splitlines() if re.match(r"\s*(ROOT )?%", line)]


def step_instructions(text):
    return [(n, op, name) for n, op, name in INSTR.findall(text)
            if name.startswith("jit(step)/")]


def test_every_step_instruction_is_under_a_phase(compiled_text):
    instrs = step_instructions(compiled_text)
    assert len(instrs) > 500
    unscoped = [i for i in instrs if not phases_of(i[2])]
    assert not unscoped, unscoped[:10]
    top = {phases_of(name)[0] for _, _, name in instrs}
    assert top == set(telemetry.PHASES) - {"grad_reduce", "curvature_product"}


def test_loops_sit_in_their_phases(compiled_text):
    loops = {name for _, op, name in step_instructions(compiled_text)
             if op == "while"}
    assert loops == {"jit(step)/krylov_solve/while",
                     "jit(step)/line_search/while"}


def test_products_sit_in_curvature_product(compiled_text):
    """Every matrix product of the Krylov solve is a curvature product, in
    the loop and in the initial residual; the primal's are not."""
    dots = [(n, name) for n, op, name in step_instructions(compiled_text)
            if op in ("dot", "convolution")]
    solve = [name for _, name in dots if "krylov_solve" in phases_of(name)]
    assert any("/while/body/curvature_product/" in n for n in solve)
    assert all(phases_of(n)[-1] == "curvature_product" for n in solve), solve
    primal = [name for _, name in dots
              if phases_of(name)[0] == "curvature_primal"]
    assert primal and not any("curvature_product" in n for n in primal)


@pytest.mark.parametrize("solver", ["gn_cg", "hybrid_cg"])
def test_gauss_newton_products_are_scoped_too(solver):
    opt, params, batch = timit_step(solver, max_cg_iters=3)
    text = jax.jit(opt.step).lower(params, opt.init(params),
                                   batch).compile().as_text()
    instrs = step_instructions(text)
    assert not [i for i in instrs if not phases_of(i[2])]
    assert any("/curvature_product/" in name for _, _, name in instrs)


def test_phase_rejects_an_unknown_name():
    with pytest.raises(ValueError, match="unknown phase"):
        with telemetry.phase("krylov"):
            pass


def test_phase_adds_no_operation():
    def f(x):
        with telemetry.phase("line_search"):
            return jnp.tanh(x) * 2.0

    def g(x):
        return jnp.tanh(x) * 2.0

    x = jnp.ones((8,))
    assert str(jax.make_jaxpr(f)(x)) == str(jax.make_jaxpr(g)(x))
    assert instructions(jax.jit(f).lower(x).compile().as_text()) == (
        instructions(jax.jit(g).lower(x).compile().as_text()))
    assert "line_search/tanh" in jax.jit(f).lower(x).as_text(debug_info=True)


@pytest.fixture
def fresh_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own, every
    program kept; JAX's settings restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield tmp_path
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def scoped(name):
    def f(x):
        with telemetry.phase(name):
            return jnp.tanh(x @ x.T).sum()
    return f


def test_names_survive_the_compile_cache(fresh_cache, monkeypatch):
    """Compile, reload from the cache, and the scope is still there; a
    program that differs only in its scopes is another entry, never a hit
    on the old one (launch/cache.py puts the metadata in the key). The key
    then holds the call's source locations too, so all three compile from
    one line."""
    from repro.launch import cache

    monkeypatch.delenv(cache.ENV, raising=False)
    monkeypatch.setattr(cache, "DEFAULT_DIR", str(fresh_cache))
    assert cache.enable_compile_cache() == str(fresh_cache)
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    x = jnp.ones((16, 16))
    events = []
    listener = lambda event, **_: events.append(event.rsplit("/", 1)[-1])
    jax.monitoring.register_event_listener(listener)
    texts = []
    try:
        for name in ("krylov_solve", "krylov_solve", "line_search"):
            jax.clear_caches()
            texts.append(jax.jit(scoped(name)).lower(x).compile().as_text())
    finally:
        jax.monitoring.unregister_event_listener(listener)
    looked_up = [e for e in events if e in ("cache_hits", "cache_misses")]
    assert looked_up[-3:] == ["cache_misses", "cache_hits", "cache_misses"]
    assert "krylov_solve/" in texts[0] and "krylov_solve/" in texts[1]
    assert "line_search/" in texts[2] and "krylov_solve/" not in texts[2]


def test_host_span_lands_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    sink = telemetry.Telemetry(str(tmp_path / "events"))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    with sink.span("host_step", step=0):
        jax.block_until_ready(jnp.ones((4,)) * 2.0)
    jax.profiler.stop_trace()
    sink.close()
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    host = [e.name for p in data.planes if p.name.startswith("/host")
            for line in p.lines for e in line.events]
    assert "host_step" in host
