"""Deterministic synthetic data: LM token streams per model family and
MNIST/TIMIT-like classification sets for the paper's own experiments.

Everything is generated from PRNG keys — no downloads, reproducible, and the
class structure is learnable (Gaussian class prototypes + noise) so optimizer
comparisons (Fig. 3/4) show real convergence differences.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------- LM batches --
def lm_batch(key, cfg, batch_size: int, seq_len: int):
    """Synthetic next-token batch for any assigned architecture.

    Tokens follow a noisy periodic process so there is learnable structure.
    The vlm family gets stubbed patch embeddings; the audio family gets
    log-mel frames (``mel``: 2·n_audio_frames frames of n_mels bins, which
    the conv stem halves to the encoder's positions).
    """
    k1, k2, k3 = jax.random.split(key, 3)
    text_len = seq_len - (cfg.n_vision_tokens if cfg.family == "vlm" else 0)
    base = jax.random.randint(k1, (batch_size, 1), 0, cfg.vocab_size)
    drift = jnp.cumsum(jax.random.randint(k2, (batch_size, text_len), 0, 7) - 3, axis=1)
    stream = jnp.mod(base + drift, cfg.vocab_size).astype(jnp.int32)
    tokens = stream[:, :-1]
    targets = stream[:, 1:]
    # pad to text_len (keep shapes uniform): repeat last column
    tokens = jnp.concatenate([tokens, tokens[:, -1:]], axis=1)
    targets = jnp.concatenate([targets, targets[:, -1:]], axis=1)
    batch = {
        "tokens": tokens,
        "targets": targets,
        "loss_mask": jnp.ones((batch_size, text_len), jnp.float32),
    }
    if cfg.family == "vlm":
        batch["vision_embed"] = jax.random.normal(
            k3, (batch_size, cfg.n_vision_tokens, cfg.vision_dim), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        batch["mel"] = jax.random.normal(
            k3, (batch_size, 2 * cfg.n_audio_frames, cfg.n_mels), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    return batch


def batch_spec(cfg, batch_size: int, seq_len: int, kind: str = "train"):
    """ShapeDtypeStruct stand-ins mirroring ``lm_batch`` (dry-run inputs)."""
    text_len = seq_len - (cfg.n_vision_tokens if cfg.family == "vlm" else 0)
    sds = jax.ShapeDtypeStruct
    spec = {
        "tokens": sds((batch_size, text_len), jnp.int32),
        "targets": sds((batch_size, text_len), jnp.int32),
        "loss_mask": sds((batch_size, text_len), jnp.float32),
    }
    if cfg.family == "vlm":
        spec["vision_embed"] = sds(
            (batch_size, cfg.n_vision_tokens, cfg.vision_dim), jnp.dtype(cfg.dtype)
        )
    if cfg.family == "audio":
        spec["mel"] = sds(
            (batch_size, 2 * cfg.n_audio_frames, cfg.n_mels), jnp.dtype(cfg.dtype)
        )
    return spec


def decode_inputs(key, cfg, batch_size: int):
    """One decode-step token batch."""
    return jax.random.randint(key, (batch_size, 1), 0, cfg.vocab_size).astype(jnp.int32)


def iterate_batches(key, cfg, batch_size, seq_len, steps):
    for i in range(steps):
        yield lm_batch(jax.random.fold_in(key, i), cfg, batch_size, seq_len)


# ------------------------------------------- classification (paper repro) --
def classification_dataset(key, n: int, d: int, n_classes: int, noise: float = 1.0):
    """Gaussian class prototypes + isotropic noise: learnable, MNIST-like
    dimensions, deterministic. Returns {"x": (n,d), "y": (n,)}."""
    kp, kx, ky = jax.random.split(key, 3)
    protos = jax.random.normal(kp, (n_classes, d)) * 2.0
    y = jax.random.randint(ky, (n,), 0, n_classes)
    x = protos[y] + jax.random.normal(kx, (n, d)) * noise
    return {"x": x.astype(jnp.float32), "y": y.astype(jnp.int32)}


def minibatches(data, batch_size: int, *, seed: int = 0, epochs: int = 1):
    """Shuffled mini-batch iterator over a classification dataset."""
    n = data["x"].shape[0]
    rng = np.random.RandomState(seed)
    for _ in range(epochs):
        perm = rng.permutation(n)
        for i in range(0, n - batch_size + 1, batch_size):
            idx = perm[i : i + batch_size]
            yield {"x": data["x"][idx], "y": data["y"][idx]}
