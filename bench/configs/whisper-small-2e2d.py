"""whisper-small, written plainly: weights from the seed, the reference
loss, and the matmul FLOPs of each pass.

The reference imports nothing of the program. It is whisper as Radford et
al., "Robust Speech Recognition via Large-Scale Weak Supervision"
(arXiv:2212.04356) and the published ``openai/whisper-small`` define it,
in straightforward ``jax.numpy``:

* the audio stem: conv width 3 (padding 1) from the mel bins to d_model,
  exact GELU, conv width 3 stride 2 (padding 1), exact GELU, then fixed
  sinusoidal positions (sines over the first half of the channels, cosines
  over the second, timescales 1 to 10000);
* pre-LN residual blocks with layer norm (eps 1e-5, scale and bias): the
  encoder's bidirectional self-attention and MLP; the decoder's causal
  self-attention, cross-attention over the encoder's output and MLP;
* attention with biases on the query, value and output projections and none
  on the key, softmax(q k^T / sqrt(d_head)) materialised in full;
* MLP d_model -> ffn -> d_model with exact (erf) GELU, biases on both;
* learned decoder positions added to the token embedding;
* a final layer norm on each side, the logits from the token embedding
  itself (the tied head) over the whole vocabulary;
* the mean over unmasked positions (``loss_mask``) of the softmax
  cross-entropy of ``targets``.

Departures from the paper: the depth is cut (``cfg["depth"]``), and the
weights are random.

The caller sets the matmul precision (``jax.default_matmul_precision``;
"highest" for float32 on a TPU). ``dtype`` is the precision of every
operation. The parameters are the program's pytree: stacked layers under
``enc_blocks`` and ``dec_blocks``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# ------------------------------------------------------------------ weights --
def _dense(key, d_in, d_out, bias):
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32)
              / jnp.sqrt(float(d_in))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def _norm(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def _attn(key, d):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {"wq": _dense(kq, d, d, True), "wk": _dense(kk, d, d, False),
            "wv": _dense(kv, d, d, True), "wo": _dense(ko, d, d, True)}


def _mlp(key, d, ffn):
    ki, ko = jax.random.split(key)
    return {"wi": _dense(ki, d, ffn, True), "wo": _dense(ko, ffn, d, True)}


def _conv(key, d_in, d_out):
    return {"w": jax.random.normal(key, (3, d_in, d_out), jnp.float32)
                 / jnp.sqrt(3.0 * d_in),
            "b": jnp.zeros((d_out,), jnp.float32)}


def init_params(key, cfg):
    """The program's pytree, float32: dense and conv weights N(0, 1/fan_in),
    the token embedding and the learned decoder positions N(0, 0.02^2),
    biases 0, layer norms scale 1 and bias 0."""
    d, depth = cfg["d_model"], cfg["depth"]
    k1, k2, ke, kp, kenc, kdec = jax.random.split(key, 6)

    def enc(k):
        ka, km = jax.random.split(k)
        return {"norm1": _norm(d), "attn": _attn(ka, d), "norm2": _norm(d),
                "mlp": _mlp(km, d, cfg["encoder_ffn_dim"])}

    def dec(k):
        ks, kc, km = jax.random.split(k, 3)
        return {"norm1": _norm(d), "self_attn": _attn(ks, d),
                "norm2": _norm(d), "cross_attn": _attn(kc, d),
                "norm3": _norm(d), "mlp": _mlp(km, d, cfg["decoder_ffn_dim"])}

    return {
        "conv1": _conv(k1, cfg["num_mel_bins"], d),
        "conv2": _conv(k2, d, d),
        "enc_blocks": jax.vmap(enc)(
            jax.random.split(kenc, depth["encoder_layers"])),
        "enc_norm": _norm(d),
        "embed": {"table": jax.random.normal(ke, (cfg["vocab_size"], d),
                                             jnp.float32) * 0.02},
        "dec_pos": jax.random.normal(kp, (cfg["max_target_positions"], d),
                                     jnp.float32) * 0.02,
        "dec_blocks": jax.vmap(dec)(
            jax.random.split(kdec, depth["decoder_layers"])),
        "final_norm": _norm(d),
    }


# ---------------------------------------------------------------- reference --
def _sinusoids(length, channels):
    inc = jnp.log(10000.0) / (channels // 2 - 1)
    inv = jnp.exp(-inc * jnp.arange(channels // 2, dtype=jnp.float32))
    t = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=1)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def _layer_norm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _linear(p, x):
    y = x @ p["w"]
    return y + p["b"] if "b" in p else y


def _conv1d(p, x, stride):
    """Width-3 convolution, zero padding 1, as three shifted products."""
    T = x.shape[1]
    out = (T - 1) // stride + 1
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0)))
    y = sum(xp[:, k:k + stride * (out - 1) + 1:stride] @ p["w"][k]
            for k in range(3))
    return y + p["b"]


def _attention(p, x, src, heads, causal):
    B, S, d = x.shape
    T = src.shape[1]
    hd = d // heads
    q = _linear(p["wq"], x).reshape(B, S, heads, hd)
    k = _linear(p["wk"], src).reshape(B, T, heads, hd)
    v = _linear(p["wv"], src).reshape(B, T, heads, hd)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(hd).astype(x.dtype)
    if causal:
        keep = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(keep, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", a, v).reshape(B, S, d)
    return _linear(p["wo"], o)


def _mlp_apply(p, x):
    return _linear(p["wo"], _gelu(_linear(p["wi"], x)))


def reference_logits(params, batch, cfg, dtype=jnp.float32):
    """The logits (B, S, vocab). The layers run as a scan over the stacked
    weights, each layer rematerialised."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eh, dh = cfg["encoder_attention_heads"], cfg["decoder_attention_heads"]
    x = _gelu(_conv1d(params["conv1"], batch["mel"].astype(dtype), 1))
    x = _gelu(_conv1d(params["conv2"], x, 2))
    x = x + _sinusoids(x.shape[1], x.shape[2]).astype(dtype)

    def enc_layer(x, u):
        h = _layer_norm(u["norm1"], x)
        x = x + _attention(u["attn"], h, h, eh, causal=False)
        return x + _mlp_apply(u["mlp"], _layer_norm(u["norm2"], x)), None

    x, _ = jax.lax.scan(jax.checkpoint(enc_layer), x, params["enc_blocks"])
    enc = _layer_norm(params["enc_norm"], x)

    def dec_layer(x, u):
        h = _layer_norm(u["norm1"], x)
        x = x + _attention(u["self_attn"], h, h, dh, causal=True)
        x = x + _attention(u["cross_attn"], _layer_norm(u["norm2"], x), enc,
                           dh, causal=False)
        return x + _mlp_apply(u["mlp"], _layer_norm(u["norm3"], x)), None

    table = params["embed"]["table"]
    tokens = batch["tokens"]
    x = table[tokens] + params["dec_pos"][:tokens.shape[1]]
    x, _ = jax.lax.scan(jax.checkpoint(dec_layer), x, params["dec_blocks"])
    return _layer_norm(params["final_norm"], x) @ table.T


def reference_loss(params, batch, cfg, dtype=jnp.float32):
    """Mean cross-entropy over the unmasked positions, computed in
    ``dtype`` (the caller sets the matmul precision)."""
    logp = jax.nn.log_softmax(reference_logits(params, batch, cfg, dtype),
                              axis=-1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch["loss_mask"].astype(dtype)
    return jnp.sum(nll * mask) / jnp.sum(mask)


# -------------------------------------------------------------------- FLOPs --
def matmuls(cfg, rows):
    """The forward pass's matrix products for ``rows`` utterances, as
    (flops, n_dep), 2·m·k·n FLOPs each (``bench/flops.py``). Only the first
    conv's input is data (n_dep 1); every other product's operands are
    weights or activations (n_dep 2). Attention counts q k^T and the
    weighted values over every (query, key) pair, the causal ones too; the
    cross-attention's keys and values are projections of the 1500 encoder
    positions; the tied head is a product with the token embedding."""
    d, V = cfg["d_model"], cfg["vocab_size"]
    T, S = cfg["max_source_positions"], cfg["max_target_positions"]
    F, M = 2 * T, cfg["num_mel_bins"]
    r = float(rows)
    out = [(2 * r * F * 3 * M * d, 1), (2 * r * T * 3 * d * d, 2)]
    enc = [2 * r * T * d * d] * 4 + [2 * r * T * T * d] * 2 \
        + [2 * r * T * d * cfg["encoder_ffn_dim"]] * 2
    dec = ([2 * r * S * d * d] * 4 + [2 * r * S * S * d] * 2
           + [2 * r * S * d * d] * 2 + [2 * r * T * d * d] * 2
           + [2 * r * S * T * d] * 2
           + [2 * r * S * d * cfg["decoder_ffn_dim"]] * 2)
    out += [(f, 2) for f in enc * cfg["depth"]["encoder_layers"]]
    out += [(f, 2) for f in dec * cfg["depth"]["decoder_layers"]]
    out.append((2 * r * S * d * V, 2))
    return out
