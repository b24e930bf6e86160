"""GQA attention: training/prefill (full-sequence) and single-token decode
against a rolling KV cache (bounded by the sliding window when configured).

The training-path entry points (``attend_full``: causal self- and
cross-attention; ``encoder_attend``) run inside ``jax.named_scope
("attention")``, so every device operation of the attention sublayer,
projections included, carries ``attention`` in its ``op_name``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense, dense_init

NEG_INF = -1e30


def attn_init(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d, H * hd, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(k2, d, KV * hd, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(k3, d, KV * hd, dtype, bias=cfg.qkv_bias),
        "wo": dense_init(k4, H * hd, d, dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _sdpa(q, k, v, mask):
    """q:(B,S,H,hd) k,v:(B,T,KV,hd) mask:(B|1,1,S,T) -> (B,S,H,hd).

    GQA: H queries share H/KV kv-heads; computed grouped to avoid
    materializing repeated K/V.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    # Keep K/V in their storage dtype (casting a 32k-deep decode cache to f32
    # would double-materialize it in HBM); accumulate the contractions in f32.
    logits = jnp.einsum(
        "bskgh,btkh->bkgst", qg, k, preferred_element_type=jnp.float32
    )
    logits = logits / jnp.sqrt(hd).astype(jnp.float32)
    logits = logits + jnp.where(mask[:, :, None], 0.0, NEG_INF)  # mask:(B|1,1|KV,S,T)->(.. ,1,S,T)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgst,btkh->bskgh", w.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.reshape(B, S, H, hd).astype(q.dtype)


def causal_mask(S, T=None, *, window: Optional[int] = None, offset: int = 0):
    """(1, 1, S, T) boolean; query i attends keys j with j ≤ i+offset and
    (no window) or j > i+offset-window."""
    T = T if T is not None else S
    qi = jnp.arange(S)[:, None] + offset
    kj = jnp.arange(T)[None, :]
    m = kj <= qi
    if window is not None:
        m = jnp.logical_and(m, kj > qi - window)
    return m[None, None]


@jax.named_scope("attention")
def attend_full(p, x, positions, cfg, *, mask=None, cross_kv=None):
    """Training/prefill attention. x:(B,S,d). Returns (B,S,d).

    ``cross_kv=(k_src, v_src)`` turns this into cross-attention (no mask,
    no RoPE on source side — whisper style).

    Under ``cfg.use_flash_attention`` every path runs the fully
    differentiable Pallas flash kernel (kernels.ops.flash_attention —
    forward, backward, and JVP passes, so gradients, line searches and
    every curvature product avoid the O(S²) logits): the default
    causal(/sliding-window) self-attention directly; cross-attention with
    its mismatched q/kv lengths via the kernels' pad-and-mask treatment;
    explicit (head-broadcast) masks as an additive f32 logit bias operand.
    Only per-kv-head masks (mask.shape[1] > 1, which no model config emits)
    keep the jnp ``_sdpa`` — otherwise ``_sdpa`` is the parity oracle only.
    """
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    B, S, _ = x.shape
    q = _split_heads(dense(p["wq"], x), H, hd)
    if cross_kv is None:
        k = _split_heads(dense(p["wk"], x), KV, hd)
        v = _split_heads(dense(p["wv"], x), KV, hd)
        q = apply_rope(q, positions, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
        k = apply_rope(k, positions, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
        if cfg.use_flash_attention and mask is None:
            from ..kernels import ops as kops

            out = kops.flash_attention(q, k, v, causal=True,
                                       window=cfg.sliding_window)
            return dense(p["wo"], out.reshape(B, S, H * hd))
        if mask is None:
            mask = causal_mask(S, window=cfg.sliding_window)
    else:
        k, v = cross_kv
        if cfg.use_flash_attention and mask is None:
            from ..kernels import ops as kops

            out = kops.flash_attention(q, k, v, causal=False, window=None)
            return dense(p["wo"], out.reshape(B, S, H * hd))
        if mask is None:
            mask = jnp.ones((1, 1, S, k.shape[1]), bool)
    if cfg.use_flash_attention and mask.shape[1] == 1:
        from ..kernels import ops as kops

        bias = jnp.where(mask[:, 0], 0.0, NEG_INF).astype(jnp.float32)
        out = kops.flash_attention(q, k, v, causal=False, window=None,
                                   bias=bias)
        return dense(p["wo"], out.reshape(B, S, H * hd))
    out = _sdpa(q, k, v, mask)
    return dense(p["wo"], out.reshape(B, S, H * hd))


@jax.named_scope("attention")
def encoder_attend(p, x, cfg):
    """Bidirectional self-attention (whisper encoder): no mask, no RoPE.
    Runs the non-causal flash kernel under ``cfg.use_flash_attention``."""
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    B, S, _ = x.shape
    q = _split_heads(dense(p["wq"], x), H, hd)
    k = _split_heads(dense(p["wk"], x), KV, hd)
    v = _split_heads(dense(p["wv"], x), KV, hd)
    if cfg.use_flash_attention:
        from ..kernels import ops as kops

        out = kops.flash_attention(q, k, v, causal=False, window=None)
    else:
        out = _sdpa(q, k, v, jnp.ones((1, 1, S, S), bool))
    return dense(p["wo"], out.reshape(B, S, H * hd))


# ------------------------------------------------------------- KV cache ----
class KVCache(NamedTuple):
    k: jax.Array        # (B, W, KV, hd) — rolling window buffer
    v: jax.Array        # (B, W, KV, hd)
    pos: jax.Array      # (W,) absolute position stored in each slot (-1 empty)

    @property
    def window(self):
        return self.k.shape[1]


def init_kv_cache(cfg, batch, max_len, dtype, *, ragged=False) -> KVCache:
    """Dense rolling cache. ``ragged=True`` gives per-sequence slot
    positions pos: (B, W) — the continuous-batching layout where every
    batch slot sits at its own decode position (decode_attend_ragged)."""
    W = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    pos_shape = (batch, W) if ragged else (W,)
    return KVCache(
        k=jnp.zeros((batch, W, KV, hd), dtype),
        v=jnp.zeros((batch, W, KV, hd), dtype),
        pos=jnp.full(pos_shape, -1, jnp.int32),
    )


def attend_full_with_cache(p, x, positions, cfg, max_len, dtype=None):
    """Prefill: full-sequence causal attention that also returns the KV cache
    (rolling layout: absolute position p lives in slot p % W). Uses the
    Pallas flash-attention kernel when ``cfg.use_flash_attention``;
    non-block-aligned sequences are padded, tail-masked and sliced inside
    the kernel wrapper (kernels/flash_ad.py), so there is no alignment
    gate."""
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    B, S, _ = x.shape
    q = _split_heads(dense(p["wq"], x), H, hd)
    k = _split_heads(dense(p["wk"], x), KV, hd)
    v = _split_heads(dense(p["wv"], x), KV, hd)
    q = apply_rope(q, positions, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, positions, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    if cfg.use_flash_attention:
        from ..kernels import ops as kops

        out = kops.flash_attention(q, k, v, causal=True, window=cfg.sliding_window)
    else:
        mask = causal_mask(S, window=cfg.sliding_window)
        out = _sdpa(q, k, v, mask)
    y = dense(p["wo"], out.reshape(B, S, H * hd))

    W = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    keep = min(S, W)
    pos_kept = positions[S - keep:]
    slots = jnp.mod(pos_kept, W)
    cache = KVCache(
        k=jnp.zeros((B, W) + k.shape[2:], k.dtype).at[:, slots].set(k[:, S - keep:]),
        v=jnp.zeros((B, W) + v.shape[2:], v.dtype).at[:, slots].set(v[:, S - keep:]),
        pos=jnp.full((W,), -1, jnp.int32).at[slots].set(pos_kept),
    )
    return y, cache


def decode_attend(p, x, t, cache: KVCache, cfg):
    """One-token decode. x:(B,1,d); t: scalar absolute position of this token.

    Writes (k,v) for position t into slot t % W and attends over every valid
    slot (absolute position in (t-window, t]). Under
    ``cfg.use_flash_attention`` the attend runs the split-K flash-decode
    Pallas kernel (kernels/flash_decode.py) — rolling-slot validity and the
    sliding window enter as an additive (1, W) bias row (``decode_bias``),
    so the kernel never materializes the (B, H, W) logits.
    """
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    B = x.shape[0]
    W = cache.window
    q = _split_heads(dense(p["wq"], x), H, hd)
    k = _split_heads(dense(p["wk"], x), KV, hd)
    v = _split_heads(dense(p["wv"], x), KV, hd)
    pos_t = jnp.full((1,), t, jnp.int32)
    q = apply_rope(q, pos_t, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, pos_t, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    slot = jnp.mod(t, W)
    new_k = jax.lax.dynamic_update_slice_in_dim(cache.k, k, slot, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache.v, v, slot, axis=1)
    new_pos = jax.lax.dynamic_update_slice_in_dim(cache.pos, pos_t, slot, axis=0)
    if cfg.use_flash_attention:
        from ..kernels import ops as kops

        bias = kops.decode_bias(new_pos, t, window=cfg.sliding_window)
        out = kops.flash_decode(q[:, 0], new_k, new_v, bias)[:, None]
    else:
        valid = jnp.logical_and(new_pos >= 0, new_pos <= t)
        if cfg.sliding_window:
            valid = jnp.logical_and(valid, new_pos > t - cfg.sliding_window)
        mask = valid[None, None, None, :]                  # (1,1,1,W)
        out = _sdpa(q, new_k, new_v, mask)
    y = dense(p["wo"], out.reshape(B, 1, H * hd))
    return y, KVCache(new_k, new_v, new_pos)


def decode_attend_ragged(p, x, t, cache: KVCache, cfg, *, active=None):
    """Per-slot decode (continuous batching). x:(B,1,d); t:(B,) absolute
    position of each slot's current token; cache.pos:(B,W) (init_kv_cache
    ragged=True layout).

    Every batch slot advances independently: slot b writes its (k,v) at
    cache position t[b] % W and attends its own validity row. ``active``
    (B,) bool marks live slots — inactive slots leave their cache rows
    untouched and produce a fully-masked (zero) attend, so a freed slot can
    hold garbage while waiting for the next admitted request.
    """
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    B = x.shape[0]
    W = cache.window
    q = _split_heads(dense(p["wq"], x), H, hd)
    k = _split_heads(dense(p["wk"], x), KV, hd)
    v = _split_heads(dense(p["wv"], x), KV, hd)
    pos_bt = t[:, None].astype(jnp.int32)                  # (B, 1)
    q = apply_rope(q, pos_bt, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, pos_bt, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    if active is None:
        active = jnp.ones((B,), bool)
    slot = jnp.mod(t, W)
    ar = jnp.arange(B)
    # Scatter each slot's row; inactive slots re-write their old value.
    new_k = cache.k.at[ar, slot].set(
        jnp.where(active[:, None, None], k[:, 0], cache.k[ar, slot]))
    new_v = cache.v.at[ar, slot].set(
        jnp.where(active[:, None, None], v[:, 0], cache.v[ar, slot]))
    new_pos = cache.pos.at[ar, slot].set(
        jnp.where(active, t.astype(jnp.int32), cache.pos[ar, slot]))
    from ..kernels import ops as kops

    bias = kops.decode_bias(new_pos, t, window=cfg.sliding_window)  # (B, W)
    bias = jnp.where(active[:, None], bias, NEG_INF)
    if cfg.use_flash_attention:
        out = kops.flash_decode(q[:, 0], new_k, new_v, bias)[:, None]
    else:
        out = _sdpa(q, new_k, new_v, (bias == 0.0)[:, None, None, :])
    y = dense(p["wo"], out.reshape(B, 1, H * hd))
    return y, KVCache(new_k, new_v, new_pos)


def decode_cross_attend(p, x, cross_kv, cfg):
    """Decode-time cross attention against fixed encoder K/V. Flash-decode
    kernel under ``cfg.use_flash_attention`` (all source positions valid —
    zero bias row)."""
    hd, H = cfg.resolved_head_dim, cfg.n_heads
    B = x.shape[0]
    q = _split_heads(dense(p["wq"], x), H, hd)
    k, v = cross_kv
    if cfg.use_flash_attention:
        from ..kernels import ops as kops

        bias = jnp.zeros((1, k.shape[1]), jnp.float32)
        out = kops.flash_decode(q[:, 0], k, v, bias)[:, None]
    else:
        mask = jnp.ones((1, 1, 1, k.shape[1]), bool)
        out = _sdpa(q, k, v, mask)
    return dense(p["wo"], out.reshape(B, 1, H * hd))
