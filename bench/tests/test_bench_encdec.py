"""The encoder-decoder cell (``whisper-2e2d-t448``) on the CPU: its
configuration against the program's published whisper-small, its generator,
its FLOP count against hand counts, its two readers, and ``correct`` at a
size a test run can hold. The runs skip the harness's look for a chip and
drive everything else, as ``test_bench_correct.py`` does for the MLP."""
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import faults, flops, harness, phases, scoped_product_ms
from bench import trace_reduce as tr
from bench.families import encdec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "whisper-2e2d-t448"
TINY = {"d_model": 64, "encoder_attention_heads": 4, "decoder_attention_heads": 4,
        "encoder_ffn_dim": 128, "decoder_ffn_dim": 128, "vocab_size": 512,
        "max_source_positions": 32, "max_target_positions": 24}
TINY_TRAFFIC = {"train_utterances": 64, "heldout_utterances": 8, "batch": 4,
                "min_tokens": 4, "max_tokens": 24, "pad_id": 511,
                "warm_steps": 6, "eval_every": 5, "trace_steps": 3}


@pytest.fixture(autouse=True)
def own_compile_cache(tmp_path):
    """A run turns JAX's persistent cache on for its process; keep that, and
    its settings, to this file's tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    old_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    cc.reset_cache()
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    if old_env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old_env
    cc.reset_cache()


def config_module():
    spec = importlib.util.spec_from_file_location(
        "cfg_whisper_small_2e2d",
        os.path.join(BENCH, "configs", "whisper-small-2e2d.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_cell():
    cell = harness.Cell(CELL)
    cell.cfg = dict(cell.cfg, **TINY)
    cell.traffic = dict(cell.traffic, **TINY_TRAFFIC)
    return cell


# --------------------------------------------------------- configuration --
def test_configuration_is_the_programs_whisper_small_at_its_depth():
    from repro.configs import get_config

    cfg = harness.Cell(CELL).cfg
    assert cfg["reduced"] == ["depth"]
    assert encdec.model_config(cfg) == get_config("whisper-small").replace(
        n_encoder_layers=2, n_layers=2, dtype="float32",
        use_flash_attention=True)


def test_a_configuration_whose_tree_is_not_the_programs_is_refused():
    cell = tiny_cell()
    mod = config_module()

    def wrong(key, cfg):
        return dict(mod.init_params(key, cfg), extra=jnp.zeros(()))

    with pytest.raises(ValueError, match="parameter tree"):
        encdec.build(cell.cfg, cell.traffic,
                     type("M", (), {"init_params": staticmethod(wrong)}), 1)


# ------------------------------------------------------------- generator --
def test_generator_repeats_for_a_seed_and_keeps_its_lengths():
    cell = harness.Cell(CELL)
    traffic = dict(cell.traffic, train_utterances=48, heldout_utterances=8)
    cfg = dict(cell.cfg, max_source_positions=16)
    a = encdec.corpus(jax.random.PRNGKey(3), traffic, cfg)
    b = encdec.corpus(jax.random.PRNGKey(3), traffic, cfg)
    c = encdec.corpus(jax.random.PRNGKey(4), traffic, cfg)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["train"]["tokens"]),
                              np.asarray(c["train"]["tokens"]))
    train = a["train"]
    assert train["mel"].shape == (48, 32, 80)
    assert train["tokens"].shape == train["targets"].shape == (48, 448)
    mask = np.asarray(train["loss_mask"]) > 0
    lengths = mask.sum(axis=1)
    assert lengths.min() >= 32 and lengths.max() <= 448
    assert len(set(lengths.tolist())) > 10
    # a prefix of each row is kept, the rest padded
    assert np.all(mask[:, :-1] >= mask[:, 1:])
    tokens, targets = np.asarray(train["tokens"]), np.asarray(train["targets"])
    assert np.all(tokens[~mask] == 50257) and np.all(targets[~mask] == 50257)
    # the targets are the tokens shifted by one
    np.testing.assert_array_equal(tokens[:, 1:][mask[:, 1:]],
                                  targets[:, :-1][mask[:, 1:]])
    assert tokens.min() >= 0 and tokens.max() < cfg["vocab_size"]


def test_token_ids_follow_the_ranked_zipf_law():
    rank_key = jax.random.PRNGKey(2212)
    ids = np.asarray(encdec.zipf_ids(jax.random.PRNGKey(0), (200000,), 1000,
                                     1.1, rank_key))
    perm = np.asarray(jax.random.permutation(rank_key, 1000))
    counts = np.bincount(ids, minlength=1000)[perm]     # by rank
    w = np.arange(1, 1001, dtype=np.float64) ** -1.1
    want = w / w.sum() * ids.size
    np.testing.assert_allclose(counts[:5], want[:5], rtol=0.03)
    assert counts[0] > counts[9] > counts[99]


# ----------------------------------------------------------------- FLOPs --
def test_matmuls_by_hand_at_a_small_shape():
    mod = config_module()
    cfg = {"d_model": 4, "encoder_attention_heads": 2,
           "decoder_attention_heads": 2, "encoder_ffn_dim": 8,
           "decoder_ffn_dim": 8, "vocab_size": 10, "num_mel_bins": 3,
           "max_source_positions": 5, "max_target_positions": 6,
           "depth": {"encoder_layers": 1, "decoder_layers": 1}}
    mms = mod.matmuls(cfg, 2)
    F, S, d, hd = 5, 6, 4, 2
    conv = [(2 * 2 * 10 * 9 * 4, 1), (2 * 2 * 5 * 12 * 4, 2)]
    enc = ([(2 * 2 * F * d * d, 2)] * 4 + [(2 * 2 * 2 * F * F * hd, 2)] * 2
           + [(2 * 2 * F * d * 8, 2)] * 2)
    dec = ([(2 * 2 * S * d * d, 2)] * 4 + [(2 * 2 * 2 * S * S * hd, 2)] * 2
           + [(2 * 2 * S * d * d, 2)] * 2 + [(2 * 2 * F * d * d, 2)] * 2
           + [(2 * 2 * 2 * S * F * hd, 2)] * 2 + [(2 * 2 * S * d * 8, 2)] * 2)
    head = [(2 * 2 * S * d * 10, 2)]
    assert mms == conv + enc + dec + head
    assert flops.hvp(mms) == 2 * conv[0][0] + 6 * sum(f for f, _ in mms[1:])


def test_forward_at_full_width_is_an_eighth_of_a_teraflop():
    mod = config_module()
    cfg = harness.Cell(CELL).cfg
    per_seq = flops.forward(mod.matmuls(cfg, 1))
    conv = 2 * 3000 * 240 * 768 + 2 * 1500 * 2304 * 768
    enc = 2 * (4 * 2 * 1500 * 768 ** 2 + 2 * 2 * 12 * 1500 ** 2 * 64
               + 2 * 2 * 1500 * 768 * 3072)
    dec = 2 * (4 * 2 * 448 * 768 ** 2 + 2 * 2 * 12 * 448 ** 2 * 64
               + 2 * 2 * 448 * 768 ** 2 + 2 * 2 * 1500 * 768 ** 2
               + 2 * 2 * 12 * 448 * 1500 * 64 + 2 * 2 * 448 * 768 * 3072)
    head = 2 * 448 * 768 * 51865
    assert per_seq == conv + enc + dec + head
    assert 0.125e12 < per_seq < 0.127e12


# ---------------------------------------------------------------- readers --
def op(start, end, op_name, module="jit_step"):
    return phases.Op(start, end, module, op_name, "%x = f32[] x()")


def test_scoped_product_time_by_hand():
    """Only the operations under both ``curvature_product`` and the scope
    count, a loop with its body; the gradient's, the line search's and
    another module's do not."""
    k = "jit(step)/krylov_solve/while"
    cp = k + "/body/curvature_product"
    ops = [op(0, 10, "jit(step)/grad_build/attention/dot_general"),
           op(10, 60, k),
           op(12, 20, cp + "/transpose(jvp(attention))/dot_general"),
           op(20, 26, cp + "/jvp(lm_head)/dot_general"),
           op(26, 30, cp + "/jvp(mlp)/dot_general"),
           op(30, 40, None),
           op(32, 38, cp + "/attention/chunked_attention/dot_general"),
           op(60, 70, "jit(step)/line_search/while/body/lm_head/exp"),
           op(70, 80, "jit(heldout_loss)/attention/dot_general",
              "jit_heldout_loss")]
    assert scoped_product_ms.scoped_ns(ops, 0, 100, "attention") == 8 + 10
    assert scoped_product_ms.scoped_ns(ops, 0, 100, "lm_head") == 6
    assert scoped_product_ms.scoped_ns(ops, 0, 100, "conv") == 0
    assert scoped_product_ms.scoped_ns(ops, 0, 16, "attention") == 4
    assert scoped_product_ms.carries(ops, "lm_head")
    assert not scoped_product_ms.carries(ops, "conv")


def test_a_trace_without_tpu_operations_reads_nothing():
    ctx = {"trace": tr.Trace({}, [("hf_step", 0, 10)]), "lo": 0, "hi": 10,
           "devices": [0], "traced_steps": [{"cg_iters": 16.0}]}
    assert scoped_product_ms.ms_per_product(ctx, "attention") is None


# --------------------------------------------------------------- correct --
def run(cell, fault=None, seconds=0.5):
    device = harness.device_record(jax, cell.chips, {}, require_tpu=False)
    return harness.run(jax, cell, 3, seconds, False, device,
                       {"flops_per_s": 1e12}, time.perf_counter(), fault=fault)


def test_sound_run_is_correct():
    out = run(tiny_cell())
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"step_ms", "setup_s"}
    assert set(out["checks"]) == {"grad_norm_gap", "compiles_in_window"}


def test_half_batch_is_not_correct():
    out = run(tiny_cell(), fault=faults.half_batch)
    assert out["correct"] is False
    assert out["checks"]["grad_norm_gap"]["value"] > \
        out["checks"]["grad_norm_gap"]["limit"]
