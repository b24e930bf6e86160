"""Sequence-sharded decode attention (flash-decode) via shard_map.

For single-sequence long-context decode (long_500k: batch=1) neither the
batch dim nor a small kv-head count can shard the KV cache, and GSPMD's only
automatic option is to replicate/gather it. The right manual schedule shards
the cache's *sequence slots* across the model axis: every chip runs the
split-K flash-decode kernel (kernels/flash_decode.py) over its local slots
— emitting the per-shard (o, m, l) contract via ``return_stats`` — and the
partials merge with the same numerically-stable logsumexp combine the
kernel uses between its own splits (the combine is associative): two tiny
all-reduces of (B,H)-shaped stats + one (B,H,hd) partial sum, instead of
moving the cache.

This is a beyond-paper serving optimization (the paper trains MLPs); it
composes with the rolling-buffer semantics because slot position p % W maps
each chip to an interleaved slice of positions, and the mask rides in the
shared ``decode_bias`` row computed per shard from the local slot positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels import ops as kops
from .attention import KVCache, _split_heads
from .layers import apply_rope, dense

NEG_INF = -1e30


def combine_shard_stats(o, m, l, axis):
    """Merge per-shard flash-decode partials across a mesh axis.

    o: (B, H, hd) shard-local normalized output; m/l: (B, H) shard-local
    running max / softmax mass (the ``flash_decode(return_stats=True)``
    contract). Same logsumexp algebra as kernels.flash_decode.combine_splits,
    expressed as collectives: m* = pmax(m), w = l·e^{m−m*}, then one psum
    for the mass and one for the weighted outputs.
    """
    m_glob = jax.lax.pmax(m, axis)                            # (B, H)
    m_safe = jnp.where(m_glob <= NEG_INF / 2, 0.0, m_glob)
    w = l * jnp.exp(m - m_safe)                               # 0 when masked
    l_glob = jax.lax.psum(w, axis)
    o_glob = jax.lax.psum(o.astype(jnp.float32) * w[..., None], axis)
    return o_glob / jnp.maximum(l_glob, 1e-20)[..., None]


def sharded_decode_attend(p, x, t, cache: KVCache, cfg, mesh, *, axis="model",
                          interpret=None):
    """One-token decode with the cache's W dim sharded over ``axis``.

    x: (B,1,d); cache.k/v: (B,W,KV,hd) sharded P(None, axis, None, None);
    cache.pos: (W,) sharded P(axis). Returns (y: (B,1,d), new cache).
    Each shard runs the split-K flash-decode kernel on its local slots;
    the bias row comes from ``decode_bias`` on the local slot positions, so
    sharded and unsharded decode share one mask definition.
    """
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    B = x.shape[0]
    W = cache.window
    n_shards = mesh.shape[axis]
    assert W % n_shards == 0, (W, n_shards)

    q = _split_heads(dense(p["wq"], x), H, hd)
    k = _split_heads(dense(p["wk"], x), KV, hd)
    v = _split_heads(dense(p["wv"], x), KV, hd)
    pos_t = jnp.full((1,), t, jnp.int32)
    q = apply_rope(q, pos_t, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, pos_t, rope_fraction=cfg.rope_fraction, theta=cfg.rope_theta)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, axis, None, None), P(None, axis, None, None), P(axis)),
        out_specs=(P(), P(None, axis, None, None), P(None, axis, None, None), P(axis)),
        check_vma=False,  # the kernel's out_shape declares no vma
    )
    def attend(q, k_new, v_new, k_sh, v_sh, pos_sh):
        # local slot index of the global rolling slot t % W, if it lands here
        Wl = k_sh.shape[1]
        shard_id = jax.lax.axis_index(axis)
        slot_global = jnp.mod(t, W)
        slot_local = slot_global - shard_id * Wl
        mine = jnp.logical_and(slot_local >= 0, slot_local < Wl)
        sl = jnp.clip(slot_local, 0, Wl - 1)
        k_upd = jax.lax.dynamic_update_slice_in_dim(k_sh, k_new, sl, axis=1)
        v_upd = jax.lax.dynamic_update_slice_in_dim(v_sh, v_new, sl, axis=1)
        pos_upd = jax.lax.dynamic_update_slice_in_dim(pos_sh, pos_t, sl, axis=0)
        k_sh = jnp.where(mine, k_upd, k_sh)
        v_sh = jnp.where(mine, v_upd, v_sh)
        pos_sh = jnp.where(mine, pos_upd, pos_sh)

        bias = kops.decode_bias(pos_sh, t, window=cfg.sliding_window)
        o, m, l = kops.flash_decode(q[:, 0], k_sh, v_sh, bias,
                                    interpret=interpret, return_stats=True)
        out = combine_shard_stats(o, m, l, axis)
        return out.reshape(B, 1, H * hd).astype(q.dtype), k_sh, v_sh, pos_sh

    out, new_k, new_v, new_pos = attend(q, k, v, cache.k, cache.v, cache.pos)
    y = dense(p["wo"], out)
    return y, KVCache(new_k, new_v, new_pos)
