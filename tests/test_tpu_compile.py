"""Compile rehearsals: the main path's Pallas kernels through Mosaic, the
TPU kernel compiler, for a described (not attached) TPU v5e.

Interpret mode on CPU checks what the kernels compute; only the TPU
compiler checks that their block shapes, layouts and memory spaces are
legal on the chip. Each test lowers a kernel entry point at real widths
with ``interpret=False`` against a v5e device and asserts the kernel is in
the compiled program (``tpu_custom_call``). Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BF16 = jnp.bfloat16
# flash attention at real widths: GQA with 8 query heads on 2 kv heads, a
# 1024-token sequence; decode over a 4096-slot window / 32 pages of 128
B, S, H, KV = 2, 1024, 8, 2
W, N_PAGES, PAGE, MAX_PAGES = 4096, 64, 128, 32
# flat Krylov vectors: the TIMIT Fig. 5 network's parameter count, and
# qwen1.5-0.5b's (both not a multiple of the kernels' blocks)
N_PARAMS = (1_722_293, 463_987_712)
# the TIMIT Fig. 5 network and the curvature rows of the benchmark's two
# cells (batch 163840 and 16384 at a quarter)
TIMIT = (360, 512, 512, 512, 1973)
CURV_ROWS = (40960, 4096)
# whisper-small's attention at its published lengths: 4 utterances, 12
# heads of 64, f32; (queries, keys, causal)
WHISPER = {"encoder": (1500, 1500, False), "causal": (448, 448, True),
           "cross": (448, 1500, False)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", N_PARAMS)
@pytest.mark.parametrize("entry", ["x_update", "residual_dots", "dot2"])
def test_cg_fused_compiles(chip, entry, n):
    vec, scalar = chip((n,)), chip(())
    if entry == "x_update":
        _assert_kernel(lambda x, p, s, a, g: ops.bicgstab_x_update(
            x, p, s, a, g, interpret=False), vec, vec, vec, scalar, scalar)
    elif entry == "residual_dots":
        _assert_kernel(lambda s, a, r, g: ops.bicgstab_residual_dots(
            s, a, r, g, interpret=False), vec, vec, vec, scalar)
    else:
        _assert_kernel(lambda u, v: ops.dot2(u, v, interpret=False), vec, vec)


def test_gram_block_compiles(chip):
    """The s-step Gram kernel on s=4 Bi-CG-STAB basis blocks (rows off the
    sublane tile) of the TIMIT network's flat vectors."""
    n = N_PARAMS[0]
    _assert_kernel(lambda u, v: ops.gram_block(u, v, interpret=False),
                   chip((5, n)), chip((9, n)))


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("kernel", ["fwd", "bwd", "jvp"])
def test_flash_attention_compiles(chip, kernel, hd):
    q, kv, lse = chip((B, S, H, hd), BF16), chip((B, S, KV, hd), BF16), \
        chip((B, H, S))
    if kernel == "fwd":
        _assert_kernel(lambda q, k, v: ops.flash_attention_fwd(
            q, k, v, interpret=False), q, kv, kv)
    elif kernel == "bwd":     # Δ precompute + the dQ and dK/dV passes
        _assert_kernel(lambda q, k, v, o, l, do: ops.flash_attention_bwd(
            q, k, v, o, l, do, interpret=False), q, kv, kv, q, lse, q)
    else:
        _assert_kernel(lambda q, k, v, o, l, qt, kt, vt:
                       ops.flash_attention_jvp(q, k, v, o, l, qt, kt, vt,
                                               interpret=False),
                       q, kv, kv, q, lse, q, kv, kv)


@pytest.mark.parametrize("hd", [64, 128])
def test_flash_attention_grad_unaligned_compiles(chip, hd):
    """The differentiable entry at a length off the 128 tile (padded and
    masked): forward, dQ and dK/dV kernels under jax.grad."""
    q, kv = chip((B, 1000, H, hd), BF16), chip((B, 1000, KV, hd), BF16)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    _assert_kernel(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("kind", sorted(WHISPER))
def test_flash_attention_grad_whisper_compiles(chip, kind):
    """The gradient's flash passes at whisper's lengths, off the 128 tile:
    the encoder's 1500 frames, the decoder's 448 tokens, and the cross
    attention between them."""
    sq, sk, causal = WHISPER[kind]
    q, kv = chip((4, sq, 12, 64)), chip((4, sk, 12, 64))

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, causal=causal, interpret=False)
        return jnp.sum(o ** 2)

    _assert_kernel(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_flash_decode_compiles(chip, layout, hd):
    q = chip((B, H, hd), BF16)
    if layout == "dense":
        cache = chip((B, W, KV, hd), BF16)
        _assert_kernel(lambda q, k, v, b: ops.flash_decode(
            q, k, v, b, interpret=False), q, cache, cache, chip((B, W)))
    else:
        pool = chip((N_PAGES, PAGE, KV, hd), BF16)
        _assert_kernel(lambda q, k, v, t, b: ops.flash_decode_paged(
            q, k, v, t, b, interpret=False), q, pool, pool,
            chip((B, MAX_PAGES), jnp.int32), chip((B, MAX_PAGES * PAGE)))


@pytest.mark.parametrize("n", CURV_ROWS)
def test_mlp_hvp_compiles(chip, n):
    """The fused exact curvature product at the TIMIT net's widths."""
    acts = [chip((n, d)) for d in TIMIT[:-1]]
    errs = [chip((n, d)) for d in TIMIT[1:-1]]
    ws = [chip((a, b)) for a, b in zip(TIMIT, TIMIT[1:])]
    dbs = [chip((b,)) for b in TIMIT[1:]]
    _assert_kernel(lambda a, e, p, y, w, dw, db: ops.mlp_hvp(
        a, e, p, y, w, dw, db, interpret=False), acts, errs,
        chip((n, TIMIT[-1])), chip((n,), jnp.int32), ws, ws, dbs)


def test_hf_step_runs_mlp_hvp_in_curvature_product(chip, monkeypatch):
    """The TIMIT net's HF step as the chip compiles it: every launch of the
    fused product in the compiled step sits under ``curvature_product``."""
    from repro.configs import HFOptConfig
    from repro.models import build_mlp
    from repro.optim import make_optimizer

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    model = build_mlp(TIMIT)
    opt = make_optimizer(HFOptConfig(name="bicgstab", hvp_batch_frac=0.25),
                         model.loss_fn, model_out_fn=model.logits_fn,
                         out_loss_fn=model.out_loss_fn)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), t)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    batch = {"x": chip((CURV_ROWS[1], TIMIT[0])),
             "y": chip((CURV_ROWS[1],), jnp.int32)}
    text = jax.jit(opt.step).lower(on_chip(params), on_chip(state),
                                   batch).compile().as_text()
    launches = [re.search(r'op_name="([^"]*)"', line).group(1)
                for line in text.splitlines()
                if "tpu_custom_call" in line and "mlp_hvp" in line]
    # the initial residual's product and the loop body's two
    assert len(launches) == 3
    assert all("/curvature_product/" in name and "mlp_hvp" in name
               for name in launches)
