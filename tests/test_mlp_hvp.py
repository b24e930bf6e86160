"""The fused exact curvature product of a tanh MLP (kernels/mlp_hvp.py) and
its routing (models/mlp.py), on the CPU with the kernel in interpret mode.

The chip's route is forced with ``mlp._on_chip``; the kernel itself still
resolves interpret mode from the backend."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.curvature import make_gnvp_op, make_hvp_op, shared_primal_hvp
from repro.core.hvp import fd_hvp
from repro.kernels import ops, ref
from repro.models import build_mlp
from repro.models import mlp as M

tl = jax.tree_util.tree_leaves
# widths off the 128-lane tile, so the wrapper pads every one
WIDTHS = {2: (24, 40), 4: (24, 40, 72, 48)}


def net(depth, classes, rows, activation="tanh", seed=0):
    dims = WIDTHS[depth] + (classes,)
    model = build_mlp(dims, activation)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = model.init(k1)
    params = jax.tree_util.tree_map(      # biases off zero: db·tangents count
        lambda t: t + 0.1 * jax.random.normal(k4, t.shape), params)
    batch = {"x": jax.random.normal(k2, (rows, dims[0])),
             "y": jax.random.randint(k3, (rows,), 0, classes)}
    v = jax.tree_util.tree_map(
        lambda t: jax.random.normal(k4, t.shape), params)
    return model, params, batch, v


def plain_loss(params, batch):
    return M._plain_loss(params, batch["x"], batch["y"])


def rel(a, b):
    """Largest relative norm gap over the leaves."""
    return max(float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
               for x, y in zip(tl(a), tl(b)))


def as_tree(gw, gb):
    return [{"b": b, "w": w} for w, b in zip(gw, gb)]


def kernel_args(params, batch, v):
    _, _, (acts, errs, p) = M._forward_backward(params, batch["x"], batch["y"])
    return (acts, errs, p, batch["y"], [l["w"] for l in params],
            [t["w"] for t in v], [t["b"] for t in v])


def pallas_names(jaxpr):
    """Names of every pallas_call in a jaxpr, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += pallas_names(sub)
    return out


def kernels_in(fn, *args):
    return pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.fixture
def chip_route(monkeypatch):
    monkeypatch.setattr(M, "_on_chip", lambda: True)


# ------------------------------------------------------------------ kernel --
@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("classes", [10, 1973])
@pytest.mark.parametrize("rows", [64, 100])
def test_kernel_matches_the_rounded_oracle(rows, classes, depth):
    """Tiles of 32 rows: 100 leaves a remainder of 4 that the kernel masks.
    Kernel and oracle round the same operands to bf16; the f32 values they
    round differ only in summation order, so most cases agree to 1e-6, and
    an operand that lands on a rounding boundary moves the deepest layer's
    product by a few 1e-4."""
    _, params, batch, v = net(depth, classes, rows)
    args = kernel_args(params, batch, v)
    got = as_tree(*ops.mlp_hvp(*args, block_rows=32, interpret=True))
    want = as_tree(*ref.mlp_hvp_ref(*args))
    assert rel(got, want) < 1e-3


@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("classes", [10, 1973])
def test_oracle_unrounded_is_jvp_of_grad(classes, depth):
    _, params, batch, v = net(depth, classes, 48)
    with jax.default_matmul_precision("highest"):
        want = jax.jvp(lambda p: jax.grad(plain_loss)(p, batch),
                       (params,), (v,))[1]
        got = as_tree(*ref.mlp_hvp_ref(*kernel_args(params, batch, v),
                                       mxu_dtype=jnp.float32))
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("classes", [10, 1973])
def test_fused_product_matches_jvp_of_grad_and_fd(chip_route, classes, depth):
    """The product the curvature engine builds on the chip's route, against
    plain forward-over-reverse at f32 and the finite-difference oracle: the
    gap is the kernel's bf16 operands."""
    model, params, batch, v = net(depth, classes, 100)
    got = make_hvp_op(model.loss_fn, params, batch)(v)
    assert "mlp_hvp" in kernels_in(make_hvp_op(model.loss_fn, params, batch),
                                   v)
    with jax.default_matmul_precision("highest"):
        exact = make_hvp_op(plain_loss, params, batch)(v)
        fd = fd_hvp(plain_loss, params, batch, v, eps=1e-3)
    assert rel(exact, fd) < 1e-3
    assert rel(got, exact) < 1e-2
    assert rel(got, fd) < 1e-2


# ------------------------------------------------------------------- route --
@pytest.mark.parametrize("depth", [2, 4])
def test_custom_rules_give_the_plain_loss_and_gradient(chip_route, depth):
    model, params, batch, _ = net(depth, 10, 64)
    plain = lambda p, b: model.out_loss_fn(model.logits_fn(p, b), b)
    f, g = jax.value_and_grad(model.loss_fn)(params, batch)
    f0, g0 = jax.value_and_grad(plain)(params, batch)
    assert float(f) == pytest.approx(float(f0), rel=1e-6)
    assert rel(g, g0) < 1e-5
    assert float(model.loss_fn(params, batch)) == pytest.approx(
        float(plain(params, batch)), rel=1e-6)


@pytest.mark.parametrize("mode", ["naive", "linearize", "chunked"])
def test_every_curvature_mode_lands_on_the_kernel(chip_route, mode):
    model, params, batch, v = net(4, 10, 100)
    op = make_hvp_op(model.loss_fn, params, batch, mode=mode, chunk_size=32)
    assert "mlp_hvp" in kernels_in(op, v)
    want = make_hvp_op(plain_loss, params, batch, mode=mode, chunk_size=32)(v)
    assert rel(op(v), want) < 2e-2


def test_shared_primal_lands_on_the_kernel(chip_route):
    model, params, batch, v = net(4, 10, 64)
    f0, g, hvp = shared_primal_hvp(model.loss_fn, params, batch)
    assert "mlp_hvp" in kernels_in(hvp, v)
    f1, g1, hvp1 = shared_primal_hvp(plain_loss, params, batch)
    assert float(f0) == pytest.approx(float(f1), rel=1e-6)
    assert rel(g, g1) < 1e-5
    assert rel(hvp(v), hvp1(v)) < 2e-2


def test_gn_product_loss_and_gradient_carry_no_kernel(chip_route):
    model, params, batch, v = net(4, 10, 64)
    gn = make_gnvp_op(model.logits_fn, model.out_loss_fn, params, batch)
    assert kernels_in(gn, v) == []
    assert kernels_in(model.loss_fn, params, batch) == []
    assert kernels_in(jax.grad(model.loss_fn), params, batch) == []


def test_hf_steps_on_the_chips_route_follow_the_plain_ones(monkeypatch):
    """Three whole HF steps (Bi-CG-STAB on the exact Hessian) through the
    fused product follow the plain path's. On the CPU the plain path's
    matmuls are f32 and the kernel's operands bf16, which moves the solve's
    later iterations: the first step solves alike, the loss falls alike."""
    from repro.configs import HFOptConfig
    from repro.optim import make_optimizer

    model, params, batch, _ = net(4, 10, 256)
    runs = []
    for route in (False, True):
        monkeypatch.setattr(M, "_on_chip", lambda: route)
        opt = make_optimizer(HFOptConfig(name="bicgstab", hvp_batch_frac=0.25),
                             model.loss_fn, model_out_fn=model.logits_fn,
                             out_loss_fn=model.out_loss_fn)
        step, p, state, got = jax.jit(opt.step), params, opt.init(params), []
        for _ in range(3):
            p, state, m = step(p, state, batch)
            got.append((float(m["loss"]), float(m["cg_iters"])))
        runs.append(got)
    (plain, fused) = runs
    assert fused[0] == plain[0]
    np.testing.assert_allclose(fused[-1][0], plain[-1][0], rtol=1e-2)
    assert fused[-1][0] < fused[0][0]


def test_block_products_vmap_the_kernel(chip_route):
    """The s-step solvers apply the operator to stacked tangents (vmap)."""
    model, params, batch, v = net(2, 10, 64)
    op = make_hvp_op(model.loss_fn, params, batch)
    vs = jax.tree_util.tree_map(lambda t: jnp.stack([t, -2.0 * t]), v)
    got = jax.vmap(op)(vs)
    one = op(v)
    assert rel(jax.tree_util.tree_map(lambda t: t[0], got), one) < 1e-6
    assert rel(jax.tree_util.tree_map(lambda t: t[1], got),
               jax.tree_util.tree_map(lambda t: -2.0 * t, one)) < 1e-6


def test_a_direction_in_the_data_takes_plain_ad(chip_route):
    model, params, batch, v = net(2, 10, 32)
    dx = jnp.ones_like(batch["x"])
    grad_x = lambda p, x: jax.grad(model.loss_fn)(p, {"x": x, "y": batch["y"]})
    plain_x = lambda p, x: jax.grad(plain_loss)(p, {"x": x, "y": batch["y"]})
    got = jax.jvp(grad_x, (params, batch["x"]), (v, dx))[1]
    want = jax.jvp(plain_x, (params, batch["x"]), (v, dx))[1]
    assert kernels_in(lambda p, x, t, u: jax.jvp(grad_x, (p, x), (t, u)),
                      params, batch["x"], v, dx) == []
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("activation", ["relu", "sigmoid"])
def test_other_activations_fall_back(chip_route, activation):
    model, params, batch, v = net(2, 10, 32, activation=activation)
    assert kernels_in(make_hvp_op(model.loss_fn, params, batch), v) == []


def test_bf16_parameters_fall_back(chip_route):
    model, params, batch, v = net(2, 10, 32)
    params = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), params)
    v = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), v)
    assert kernels_in(make_hvp_op(model.loss_fn, params, batch), v) == []


def test_off_the_chip_the_loss_is_the_plain_one():
    """On the CPU the route is off and the loss traces exactly as the plain
    logits-then-cross-entropy composition."""
    model, params, batch, v = net(4, 10, 32)
    plain = lambda p, b: model.out_loss_fn(model.logits_fn(p, b), b)
    assert str(jax.make_jaxpr(model.loss_fn)(params, batch)) == str(
        jax.make_jaxpr(plain)(params, batch))
    assert kernels_in(make_hvp_op(model.loss_fn, params, batch), v) == []
    assert not M._on_chip()
    np.testing.assert_array_equal(
        np.asarray(model.loss_fn(params, batch)),
        np.asarray(plain(params, batch)))
