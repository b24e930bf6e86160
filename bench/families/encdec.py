"""The program side of an encoder-decoder speech cell (whisper).

Builds, through the program's public entry points only, what the harness
drives: ``repro.models.build_model`` on ``repro.configs.get_config`` of the
configuration's ``arch``, at the widths and depth its file gives, for the
network, and ``repro.optim.make_optimizer`` with ``repro.configs.HFOptConfig``
for the HF step, on one chip. The job has the fields of
``bench/families/mlp.py``'s.

The traffic's generator, ``whisper_utterances``, lives here: transcribed
utterances of 30 s, made on the device from the seed.

* ``mel`` [n, 2·max_source_positions, num_mel_bins]: N(0, 1) log-mel frames;
* ``tokens`` and ``targets`` [n, max_target_positions]: one sequence of
  Zipf-distributed token ids (exponent ``zipf_exponent``, over the whole
  vocabulary, the ranks given to ids by a permutation from the traffic's
  ``corpus_seed``), the targets the tokens shifted by one;
* ``loss_mask``: 1 over the first ``length`` positions, ``length``
  log-uniform over [``min_tokens``, ``max_tokens``]; the rest is padded with
  ``pad_id`` and masked out of the loss.

Step ``t`` takes the ``batch`` utterances from ``t · batch`` on, modulo the
training split, as one contiguous slice (``generate.timit_batch``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import generate
from bench.families.mlp import Job


def zipf_ids(key, shape, vocab: int, exponent: float, rank_key):
    """Token ids: Zipf ranks by inverse CDF, given to ids by a permutation
    drawn from ``rank_key``."""
    p = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -exponent
    cdf = jnp.cumsum(p / jnp.sum(p))
    rank = jnp.minimum(jnp.searchsorted(cdf, jax.random.uniform(key, shape)),
                       vocab - 1)
    return jax.random.permutation(rank_key, vocab)[rank].astype(jnp.int32)


def utterances(key, traffic, cfg, n: int):
    """``n`` utterances of the module docstring, on the device."""
    S, V = cfg["max_target_positions"], cfg["vocab_size"]
    km, kt, kl = jax.random.split(key, 3)
    mel = jax.random.normal(
        km, (n, 2 * cfg["max_source_positions"], cfg["num_mel_bins"]),
        jnp.float32)
    seq = zipf_ids(kt, (n, S + 1), V, traffic["zipf_exponent"],
                   jax.random.PRNGKey(traffic["corpus_seed"]))
    lo, hi = traffic["min_tokens"], traffic["max_tokens"]
    length = jnp.floor(jnp.exp(jax.random.uniform(
        kl, (n, 1), minval=jnp.log(float(lo)), maxval=jnp.log(hi + 1.0))))
    length = jnp.clip(length, lo, hi).astype(jnp.int32)
    pos = jnp.arange(S)[None]
    pad = jnp.int32(traffic["pad_id"])
    return {"mel": mel,
            "tokens": jnp.where(pos < length, seq[:, :S], pad),
            "targets": jnp.where(pos < length, seq[:, 1:], pad),
            "loss_mask": (pos < length).astype(jnp.float32)}


def corpus(key, traffic, cfg):
    """{"train", "heldout"}: the traffic's two splits, on the device."""
    ktr, khe = jax.random.split(key)
    return {"train": utterances(ktr, traffic, cfg, traffic["train_utterances"]),
            "heldout": utterances(khe, traffic, cfg,
                                  traffic["heldout_utterances"])}


def model_config(cfg):
    """The program's configuration of ``cfg``'s architecture with ``cfg``'s
    widths, depth and precision (``configs/whisper_small.py`` holds the
    published widths; ``bench/tests/test_bench_encdec.py`` checks that
    the configuration's file keeps them)."""
    from repro.configs import get_config

    return get_config(cfg["arch"]).replace(
        d_model=cfg["d_model"], n_heads=cfg["encoder_attention_heads"],
        n_kv_heads=cfg["encoder_attention_heads"], d_ff=cfg["encoder_ffn_dim"],
        vocab_size=cfg["vocab_size"], n_mels=cfg["num_mel_bins"],
        n_audio_frames=cfg["max_source_positions"],
        max_target_positions=cfg["max_target_positions"],
        n_encoder_layers=cfg["depth"]["encoder_layers"],
        n_layers=cfg["depth"]["decoder_layers"],
        dtype=cfg["dtype"], use_flash_attention=cfg["use_flash_attention"])


def build(cfg, traffic, cfg_module, chips: int) -> Job:
    from repro.configs import HFOptConfig
    from repro.models import build_model
    from repro.optim import make_optimizer

    if traffic["generator"] != "whisper_utterances":
        raise ValueError(f"an encoder-decoder cell takes whisper_utterances "
                         f"traffic, not {traffic['generator']!r}")
    if chips != 1:
        raise ValueError(f"an encoder-decoder cell runs on one chip, not {chips}")
    model = build_model(model_config(cfg))

    def shapes(init):
        return jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                      jax.eval_shape(init, jax.random.PRNGKey(0)))

    if shapes(lambda k: cfg_module.init_params(k, cfg)) != shapes(model.init):
        raise ValueError(f"{cfg['name']}: init_params' parameter tree is not "
                         f"the program's")
    opt = make_optimizer(HFOptConfig(**traffic["optimizer"]), model.loss_fn,
                         model_out_fn=model.logits_fn,
                         out_loss_fn=model.out_loss_fn)
    B, n_train = traffic["batch"], traffic["train_utterances"]
    if n_train % B:
        raise ValueError(f"batch {B} does not divide {n_train} utterances")
    frac = traffic["optimizer"].get("hvp_batch_frac", 0.25)
    n_held = traffic["heldout_utterances"]
    if n_held % B:
        raise ValueError(f"batch {B} does not divide {n_held} held-out "
                         f"utterances")

    def make_data(key):
        return jax.jit(lambda k: corpus(k, traffic, cfg))(key)

    @jax.jit
    def draw(data, step):
        return generate.timit_batch(data["train"], step, B, n_train)

    def make_params(key):
        return jax.jit(lambda k: cfg_module.init_params(k, cfg))(key)

    @jax.jit
    def heldout_loss(params, data):
        """The mean over every unmasked held-out position, ``B`` utterances
        at a time."""
        chunks = jax.tree_util.tree_map(
            lambda x: x.reshape((n_held // B, B) + x.shape[1:]),
            data["heldout"])

        def one(b):
            n = jnp.sum(b["loss_mask"])
            return model.loss_fn(params, b) * n, n

        total, count = jax.lax.map(one, chunks)
        return jnp.sum(total) / jnp.sum(count)

    def curv_rows(batch):
        n = max(int(B * frac), 1)
        return jax.tree_util.tree_map(lambda x: x[:n], batch)

    def reference_loss(params, batch, dtype=jnp.float32):
        return cfg_module.reference_loss(params, batch, cfg, dtype)

    return Job(model.loss_fn, opt, make_data, make_params, draw, heldout_loss,
               curv_rows, lambda rows: cfg_module.matmuls(cfg, rows),
               reference_loss)
