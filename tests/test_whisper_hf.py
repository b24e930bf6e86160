"""whisper's encoder-decoder (``repro.models.build_encdec_model``) against
the plain reference (``bench/configs/whisper-small-2e2d.py``, the
benchmark's own, loaded by path) at the smoke size of
``configs/whisper_small.py`` (2+2 layers, d_model 64, 4 heads, vocabulary
512, 64 mel frames) with seeded random weights, on the CPU.

Tolerances, all relative, and all far under what float32 against bfloat16
reads here (loss 1.5e-3, gradient 6.7e-2):

* loss 1e-6 — both sides compute in float32 with full-precision CPU
  matmuls; only the order of the sums differs;
* gradient and Hessian-vector product 5e-5 per leaf — the same, through
  one and two more passes of differentiation, and through attention
  computed in query blocks (the Hessian's route) or by the flash kernels;
* one HF step 1e-3 per leaf — a converged Krylov solve on top.

The HF step is compared at an initial damping of 30. At the default 1 the
smoke model's Hessian at its initial weights has curvature down to about
−14: sixteen Bi-CG-STAB iterations on that indefinite system do not
converge, and the length of the step they give (the negative-curvature
step takes the solution's norm) follows float32 rounding, so two correct
implementations part by more than the step itself. At 30 the solve
converges in four iterations and the comparison is of the mathematics.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import HFOptConfig, get_config, get_smoke_config
from repro.core.curvature import make_hvp_op
from repro.models import build_model
from repro.models.layers import conv1d
from repro.optim import make_optimizer


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "bench", "configs", "whisper-small-2e2d.py")
    spec = importlib.util.spec_from_file_location("whisper_small_2e2d", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

B, S = 2, 24
LENGTHS = (24, 13)


def model_and_params(flash=False):
    cfg = get_smoke_config("whisper-small").replace(use_flash_attention=flash)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # non-zero biases and layer-norm parameters, so that each is checked
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, params)
    return cfg, model, params


def make_batch(cfg, key=1):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(key), 3)
    return {
        "mel": jax.random.normal(k1, (B, 2 * cfg.n_audio_frames, cfg.n_mels)),
        "tokens": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(k3, (B, S), 0, cfg.vocab_size),
        "loss_mask": (jnp.arange(S)[None] < jnp.array(LENGTHS)[:, None]
                      ).astype(jnp.float32),
    }


def ref_loss(cfg):
    heads = {"encoder_attention_heads": cfg.n_heads,
             "decoder_attention_heads": cfg.n_heads}
    return lambda p, b: ref.reference_loss(p, b, heads)


def leaf_gap(a, b):
    """The largest leaf's ‖a − b‖ / ‖b‖."""
    return max(float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


@pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
def test_loss_gradient_and_hessian_product_match_the_reference(flash):
    cfg, model, params = model_and_params(flash)
    batch = make_batch(cfg)
    rl = ref_loss(cfg)
    f, g = jax.jit(jax.value_and_grad(model.loss_fn))(params, batch)
    fr, gr = jax.jit(jax.value_and_grad(rl))(params, batch)
    assert abs(float(f) - float(fr)) <= 1e-6 * abs(float(fr))
    assert leaf_gap(g, gr) < 5e-5
    v = jax.tree_util.tree_map(
        lambda a: jnp.cos(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape),
        params)
    # make_hvp_op builds the product under second_order_tangents()
    hv = jax.jit(lambda p, b, v: make_hvp_op(model.loss_fn, p, b)(v))(
        params, batch, v)
    hr = jax.jit(lambda p, b, v: jax.jvp(lambda q: jax.grad(rl)(q, b),
                                         (p,), (v,))[1])(params, batch, v)
    assert leaf_gap(hv, hr) < 5e-5


def test_stem_halves_the_mel_frames():
    cfg, model, params = model_and_params()
    _, cache = jax.jit(lambda p, b: model.prefill(p, b, S))(params, make_batch(cfg))
    assert cache["cross_k"].shape[2] == cfg.n_audio_frames == 32
    full = get_config("whisper-small")
    sds = jax.ShapeDtypeStruct
    d = full.d_model
    out = jax.eval_shape(lambda p, x: conv1d(p, x, stride=2),
                         {"w": sds((3, d, d), jnp.float32), "b": sds((d,), jnp.float32)},
                         sds((1, 2 * full.n_audio_frames, d), jnp.float32))
    assert out.shape == (1, 1500, d)


def test_head_is_the_token_embedding():
    cfg, model, params = model_and_params()
    assert "lm_head" not in params
    vocab_leaves = [x for x in jax.tree_util.tree_leaves(params)
                    if x.shape[0] == cfg.vocab_size]
    assert len(vocab_leaves) == 1 and vocab_leaves[0] is params["embed"]["table"]
    # every row of the table gets gradient, not only the rows of the tokens
    # the batch feeds in
    batch = make_batch(cfg)
    g = jax.grad(model.loss_fn)(params, batch)["embed"]["table"]
    unused = np.setdiff1d(np.arange(cfg.vocab_size), np.asarray(batch["tokens"]))
    assert np.all(np.linalg.norm(np.asarray(g)[unused], axis=1) > 0)


def test_masked_positions_give_their_targets_no_gradient():
    cfg, model, params = model_and_params()
    batch = make_batch(cfg)
    other = dict(batch, targets=jnp.where(batch["loss_mask"] > 0,
                                          batch["targets"],
                                          (batch["targets"] + 1) % cfg.vocab_size))
    assert not np.array_equal(other["targets"], batch["targets"])
    vg = jax.jit(jax.value_and_grad(model.loss_fn))
    f, g = vg(params, batch)
    f2, g2 = vg(params, other)
    assert float(f) == float(f2)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def hf_step():
    """make_optimizer's Bi-CG-STAB HF step on the flash model, compiled,
    with its inputs, at the damping where the solve converges (module
    docstring)."""
    cfg, model, params = model_and_params(flash=True)
    batch = make_batch(cfg)
    opt = make_optimizer(HFOptConfig(hvp_batch_frac=0.5, init_damping=30.0),
                         model.loss_fn)
    state = opt.init(params)
    compiled = jax.jit(opt.step).lower(params, state, batch).compile()
    return cfg, compiled, params, state, batch


def test_one_hf_step_matches_the_reference_step(hf_step):
    """One step against ``bench/hf_reference.hf_step`` over the plain loss,
    from the same parameters on the same batch (curvature on its first
    utterance)."""
    from bench import hf_reference

    cfg, compiled, params, state, batch = hf_step
    new, _, m = compiled(params, state, batch)
    assert int(m["cg_iters"]) < 16
    st = hf_reference.settings({"name": "bicgstab", "hvp_batch_frac": 0.5,
                                "init_damping": 30.0})
    curv = jax.tree_util.tree_map(lambda x: x[:1], batch)
    want, _, r = jax.jit(lambda p, s, b, c: hf_reference.hf_step(
        ref_loss(cfg), p, s, b, c, st))(
            params, hf_reference.init_state(params, st), batch, curv)
    assert abs(float(m["loss"]) - float(r["loss"])) <= 1e-6 * abs(float(r["loss"]))
    assert int(m["cg_iters"]) == int(r["cg_iters"])
    step = jax.tree_util.tree_map(jnp.subtract, new, params)
    want_step = jax.tree_util.tree_map(jnp.subtract, want, params)
    assert leaf_gap(step, want_step) < 1e-3


@pytest.mark.parametrize("scope", ["attention", "lm_head"])
def test_curvature_products_name_the_attention_and_the_head(hf_step, scope):
    """The compiled step's matrix products inside the curvature products
    carry the model's scopes in their op_name, so that a device trace gives
    each product's time to the attention and to the head."""
    import re

    text = hf_step[1].as_text()
    names = re.findall(r' (?:dot|convolution)\(.*?op_name="([^"]*)"', text)
    idents = [set(re.findall(r"[A-Za-z_]\w*", n)) for n in names]
    assert any({"curvature_product", scope} <= i for i in idents)
    assert any(scope in i and "curvature_product" not in i for i in idents)


def test_train_launcher_takes_whisper():
    from repro.launch.train import train

    _, _, hist = train("whisper-small", smoke=True, steps=1, batch_size=2,
                       seq_len=16, max_cg_iters=2, log_fn=lambda *a, **k: None)
    assert np.isfinite(hist[0]["loss"]) and hist[0]["loss_new"] <= hist[0]["loss"]
