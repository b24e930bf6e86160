"""Primitive layers: norms, MLPs, embeddings, RoPE.

Functional style: ``init_*`` builds a param dict, ``apply`` fns are pure.
All initializers take explicit PRNG keys; dtype follows the config.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


def dense_init(key, d_in, d_out, dtype, scale=None, bias=False):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def norm_init(d, dtype, kind="rmsnorm"):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def mlp_init(key, d, d_ff, dtype, act="swiglu", bias=False):
    k1, k2, k3 = jax.random.split(key, 3)
    if act == "swiglu":
        return {
            "wi": dense_init(k1, d, d_ff, dtype, bias=bias),
            "wg": dense_init(k2, d, d_ff, dtype, bias=bias),
            "wo": dense_init(k3, d_ff, d, dtype, bias=bias),
            }
    return {"wi": dense_init(k1, d, d_ff, dtype, bias=bias),
            "wo": dense_init(k3, d_ff, d, dtype, bias=bias)}


def apply_mlp(p, x):
    if "wg" in p:
        h = jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x)
    else:
        h = jax.nn.gelu(dense(p["wi"], x), approximate=False)
    return dense(p["wo"], h)


def conv_init(key, width, d_in, d_out, dtype):
    """A 1-d convolution's {"w": (width, d_in, d_out), "b": (d_out,)}:
    N(0, 1/(width·d_in)) weights, zero bias."""
    w = jax.random.normal(key, (width, d_in, d_out)) / jnp.sqrt(width * d_in)
    return {"w": w.astype(dtype), "b": jnp.zeros((d_out,), dtype)}


def conv1d(p, x, stride=1):
    """x: (B, T, d_in) -> (B, ceil(T / stride), d_out), zero-padded by
    width // 2 on each side (whisper's audio stem)."""
    pad = p["w"].shape[0] // 2
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride,), padding=[(pad, pad)],
        dimension_numbers=("NWC", "WIO", "NWC"))
    return y + p["b"]


def embedding_init(key, vocab, d, dtype):
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p, x):
    """Tied head: logits in fp32 for loss stability."""
    return x.astype(jnp.float32) @ p["table"].astype(jnp.float32).T


# ---------------------------------------------------------------- RoPE ----
def rope_freqs(head_dim, rope_fraction, theta):
    """Rotary frequencies over the first ``fraction`` of the head dim."""
    rot = int(head_dim * rope_fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, *, rope_fraction=1.0, theta=1e4):
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    ``rope_fraction < 1`` rotates only the leading slice (ChatGLM-style
    partial/2d rotary); the remainder passes through unrotated.
    """
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, rope_fraction, theta)
    if rot == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    xr = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([xr.astype(x.dtype), x[..., rot:]], axis=-1)
