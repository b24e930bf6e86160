"""Generic scanned-block transformer covering all assigned families.

Stacks are built as *stacked pytrees* (leading dim = number of repeating
units) and executed with ``lax.scan`` — essential for compile time at 512
devices with 24-81 layers. A config's ``block_pattern`` names the repeating
unit (("attn",) dense, ("moe",) MoE, ("mamba",) SSM, ("slstm","mlstm")
xLSTM); the zamba2 hybrid (mamba backbone + one weight-*shared* attention
block every ``attn_every`` layers) and the whisper encoder-decoder get their
own stack layouts.

Three execution paths per model, all pure functions:
  * full-sequence (train loss / logits — twice differentiable for HF),
  * prefill (full sequence + returns decode caches),
  * decode_step (one token against the caches).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import xlstm as xl
from .attention import (
    KVCache,
    attend_full,
    attend_full_with_cache,
    causal_mask,
    decode_attend,
    decode_attend_ragged,
    decode_cross_attend,
    encoder_attend,
    init_kv_cache,
    _sdpa,
    _split_heads,
)
from .layers import (
    apply_mlp,
    apply_norm,
    conv1d,
    conv_init,
    dense,
    dense_init,
    dtype_of,
    embed,
    embedding_init,
    mlp_init,
    norm_init,
    unembed,
)
from .moe import apply_moe, moe_init
from .ssm import (
    MambaCache,
    apply_mamba,
    init_mamba_cache,
    mamba_decode_step,
    mamba_init,
)


class ModelApi(NamedTuple):
    config: Any
    init: Callable
    loss_fn: Callable            # (params, batch) -> scalar  (twice differentiable)
    logits_fn: Callable          # (params, batch) -> (B, S, V)   [GN split: network]
    out_loss_fn: Callable        # (logits, batch) -> scalar      [GN split: loss]
    prefill: Callable            # (params, batch, max_len) -> (logits, cache)
    decode_step: Callable        # (params, token(B,1), t, cache) -> (logits, cache)
    init_cache: Callable         # (batch_size, max_len) -> cache
    # Continuous-batching / paged serving (dense-decoder stacks only; None
    # elsewhere). Ragged: every batch slot sits at its own decode position.
    decode_step_ragged: Optional[Callable] = None
    # (params, token(B,1), t(B,), cache, active(B,)) -> (logits, cache)
    init_cache_ragged: Optional[Callable] = None
    # (batch_size, max_len) -> cache with per-slot pos rows
    decode_step_paged: Optional[Callable] = None
    # (params, token(B,1), paged_cache, active(B,)) -> (logits, paged_cache)
    init_cache_paged: Optional[Callable] = None
    # (batch_size, max_len, n_pages, page_size) -> PagedKVCache
    prefill_paged: Optional[Callable] = None
    # (params, batch(1 prompt), paged_cache, slot) -> (logits, paged_cache)


# ------------------------------------------------------------------ units --
def _unit_init(key, cfg, dtype):
    parts = {}
    keys = jax.random.split(key, len(cfg.block_pattern))
    d = cfg.d_model
    for j, kind in enumerate(cfg.block_pattern):
        k = keys[j]
        name = f"b{j}_{kind}"
        if kind == "attn":
            k1, k2 = jax.random.split(k)
            from .attention import attn_init
            parts[name] = {
                "norm1": norm_init(d, dtype, cfg.norm_kind),
                "attn": attn_init(k1, cfg, dtype),
                "norm2": norm_init(d, dtype, cfg.norm_kind),
                "mlp": mlp_init(k2, d, cfg.d_ff, dtype, cfg.mlp_act),
            }
        elif kind == "moe":
            k1, k2 = jax.random.split(k)
            from .attention import attn_init
            parts[name] = {
                "norm1": norm_init(d, dtype, cfg.norm_kind),
                "attn": attn_init(k1, cfg, dtype),
                "norm2": norm_init(d, dtype, cfg.norm_kind),
                "moe": moe_init(k2, cfg, dtype),
            }
        elif kind == "mamba":
            parts[name] = {
                "norm": norm_init(d, dtype, cfg.norm_kind),
                "mamba": mamba_init(k, cfg, dtype),
            }
        elif kind == "mlstm":
            parts[name] = {
                "norm": norm_init(d, dtype, cfg.norm_kind),
                "mlstm": xl.mlstm_init(k, cfg, dtype),
            }
        elif kind == "slstm":
            parts[name] = {
                "norm": norm_init(d, dtype, cfg.norm_kind),
                "slstm": xl.slstm_init(k, cfg, dtype),
            }
    return parts


def _unit_apply(unit, x, positions, cfg, *, produce_cache=False, max_len=None):
    """Full-sequence unit. Returns (x, aux, caches-dict)."""
    aux = jnp.zeros((), jnp.float32)
    caches = {}
    for j, kind in enumerate(cfg.block_pattern):
        name = f"b{j}_{kind}"
        p = unit[name]
        if kind in ("attn", "moe"):
            h = apply_norm(p["norm1"], x, cfg.norm_eps)
            if produce_cache:
                a, kv = attend_full_with_cache(p["attn"], h, positions, cfg, max_len)
                caches[name] = kv
            else:
                a = attend_full(p["attn"], h, positions, cfg)
            x = x + a
            h = apply_norm(p["norm2"], x, cfg.norm_eps)
            if kind == "attn":
                x = x + apply_mlp(p["mlp"], h)
            else:
                mo, a_loss = apply_moe(p["moe"], h, cfg)
                x = x + mo
                aux = aux + a_loss
        elif kind == "mamba":
            h = apply_norm(p["norm"], x, cfg.norm_eps)
            y, c = apply_mamba(p["mamba"], h, cfg)
            x = x + y
            if produce_cache:
                caches[name] = c
        elif kind == "mlstm":
            h = apply_norm(p["norm"], x, cfg.norm_eps)
            y, c = xl.apply_mlstm(p["mlstm"], h, cfg)
            x = x + y
            if produce_cache:
                caches[name] = c
        elif kind == "slstm":
            h = apply_norm(p["norm"], x, cfg.norm_eps)
            y, c = xl.apply_slstm(p["slstm"], h, cfg)
            x = x + y
            if produce_cache:
                caches[name] = c
    return x, aux, caches


def _unit_decode(unit, x, t, caches, cfg):
    new_caches = {}
    for j, kind in enumerate(cfg.block_pattern):
        name = f"b{j}_{kind}"
        p = unit[name]
        c = caches[name]
        if kind in ("attn", "moe"):
            h = apply_norm(p["norm1"], x, cfg.norm_eps)
            a, new_caches[name] = decode_attend(p["attn"], h, t, c, cfg)
            x = x + a
            h = apply_norm(p["norm2"], x, cfg.norm_eps)
            if kind == "attn":
                x = x + apply_mlp(p["mlp"], h)
            else:
                mo, _ = apply_moe(p["moe"], h, cfg)
                x = x + mo
        elif kind == "mamba":
            h = apply_norm(p["norm"], x, cfg.norm_eps)
            y, new_caches[name] = mamba_decode_step(p["mamba"], h, c, cfg)
            x = x + y
        elif kind == "mlstm":
            h = apply_norm(p["norm"], x, cfg.norm_eps)
            y, new_caches[name] = xl.mlstm_decode_step(p["mlstm"], h, c, cfg)
            x = x + y
        elif kind == "slstm":
            h = apply_norm(p["norm"], x, cfg.norm_eps)
            y, new_caches[name] = xl.slstm_decode_step(p["slstm"], h, c, cfg)
            x = x + y
    return x, new_caches


def _unit_decode_ragged(unit, x, t, caches, cfg, active):
    """Per-slot decode of one unit (attn/moe blocks only — the continuous
    batching path is gated to dense-decoder stacks)."""
    new_caches = {}
    for j, kind in enumerate(cfg.block_pattern):
        name = f"b{j}_{kind}"
        p = unit[name]
        c = caches[name]
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        a, new_caches[name] = decode_attend_ragged(p["attn"], h, t, c, cfg,
                                                   active=active)
        x = x + a
        h = apply_norm(p["norm2"], x, cfg.norm_eps)
        if kind == "attn":
            x = x + apply_mlp(p["mlp"], h)
        else:
            mo, _ = apply_moe(p["moe"], h, cfg)
            x = x + mo
    return x, new_caches


def _unit_cache_zeros(cfg, batch, max_len, dtype, *, ragged=False):
    caches = {}
    for j, kind in enumerate(cfg.block_pattern):
        name = f"b{j}_{kind}"
        if kind in ("attn", "moe"):
            caches[name] = init_kv_cache(cfg, batch, max_len, dtype,
                                         ragged=ragged)
        elif kind == "mamba":
            caches[name] = init_mamba_cache(cfg, batch, dtype)
        elif kind == "mlstm":
            caches[name] = xl.init_mlstm_cache(cfg, batch)
        elif kind == "slstm":
            caches[name] = xl.init_slstm_cache(cfg, batch)
    return caches


def _stack(tree, n):
    """Replicate a cache pytree along a new leading (layer) dim."""
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree
    )


# ----------------------------------------------------- shared attn (zamba) --
def _shared_attn_init(key, cfg, dtype):
    from .attention import attn_init
    k1, k2 = jax.random.split(key)
    return {
        "norm1": norm_init(cfg.d_model, dtype, cfg.norm_kind),
        "attn": attn_init(k1, cfg, dtype),
        "norm2": norm_init(cfg.d_model, dtype, cfg.norm_kind),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype, cfg.mlp_act),
    }


def _shared_attn_apply(p, x, positions, cfg, *, produce_cache=False, max_len=None):
    h = apply_norm(p["norm1"], x, cfg.norm_eps)
    if produce_cache:
        a, kv = attend_full_with_cache(p["attn"], h, positions, cfg, max_len)
    else:
        a, kv = attend_full(p["attn"], h, positions, cfg), None
    x = x + a
    x = x + apply_mlp(p["mlp"], apply_norm(p["norm2"], x, cfg.norm_eps))
    return x, kv


def _shared_attn_decode(p, x, t, kv, cfg):
    h = apply_norm(p["norm1"], x, cfg.norm_eps)
    a, kv = decode_attend(p["attn"], h, t, kv, cfg)
    x = x + a
    x = x + apply_mlp(p["mlp"], apply_norm(p["norm2"], x, cfg.norm_eps))
    return x, kv


def hybrid_layout(cfg):
    """(n_groups, per_group, n_tail) for the zamba stack."""
    k = cfg.attn_every
    G = cfg.n_layers // k
    return G, k, cfg.n_layers - G * k


# -------------------------------------------------------------- backbones --
def _make_remat(fn, enabled):
    return jax.checkpoint(fn) if enabled else fn


def _decoder_backbone(params, x, positions, cfg, remat):
    def body(carry, unit):
        xx, aux = carry
        xx, a, _ = _unit_apply(unit, xx, positions, cfg)
        return (xx, aux + a), None

    (x, aux), _ = jax.lax.scan(_make_remat(body, remat), (x, jnp.zeros((), jnp.float32)), params["blocks"])
    return x, aux


def _decoder_backbone_prefill(params, x, positions, cfg, max_len):
    def body(carry, unit):
        xx, aux = carry
        xx, a, c = _unit_apply(unit, xx, positions, cfg, produce_cache=True, max_len=max_len)
        return (xx, aux + a), c

    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    return x, aux, caches


def _decoder_backbone_decode(params, x, t, caches, cfg):
    def body(xx, xs):
        unit, c = xs
        xx, nc = _unit_decode(unit, xx, t, c, cfg)
        return xx, nc

    x, new_caches = jax.lax.scan(body, x, (params["blocks"], caches))
    return x, new_caches


def _hybrid_backbone(params, x, positions, cfg, remat, *, produce_cache=False, max_len=None):
    G, k, R = hybrid_layout(cfg)
    shared = params["shared"]

    def inner(xx, unit):
        xx, _, c = _unit_apply(unit, xx, positions, cfg, produce_cache=produce_cache, max_len=max_len)
        return xx, c

    def outer(xx, group):
        xx, mc = jax.lax.scan(inner, xx, group)
        xx, kv = _shared_attn_apply(shared, xx, positions, cfg, produce_cache=produce_cache, max_len=max_len)
        return xx, (mc, kv)

    x, (mamba_caches, attn_caches) = jax.lax.scan(_make_remat(outer, remat), x, params["groups"])
    tail_caches = None
    if R:
        x, tail_caches = jax.lax.scan(inner, x, params["tail"])
    caches = {"groups_mamba": mamba_caches, "groups_attn": attn_caches, "tail": tail_caches}
    return x, (caches if produce_cache else None)


def _hybrid_decode(params, x, t, caches, cfg):
    shared = params["shared"]

    def inner(xx, xs):
        unit, c = xs
        xx, nc = _unit_decode(unit, xx, t, c, cfg)
        return xx, nc

    def outer(xx, xs):
        group, mc, kv = xs
        xx, nmc = jax.lax.scan(inner, xx, (group, mc))
        xx, nkv = _shared_attn_decode(shared, xx, t, kv, cfg)
        return xx, (nmc, nkv)

    x, (nmc, nkv) = jax.lax.scan(
        outer, x, (params["groups"], caches["groups_mamba"], caches["groups_attn"])
    )
    ntail = None
    if caches["tail"] is not None:
        x, ntail = jax.lax.scan(inner, x, (params["tail"], caches["tail"]))
    return x, {"groups_mamba": nmc, "groups_attn": nkv, "tail": ntail}


# ------------------------------------------------------------ build model --
def build_model(cfg, *, remat: bool = False) -> ModelApi:
    if cfg.is_encoder_decoder:
        return build_encdec_model(cfg, remat=remat)
    dtype = dtype_of(cfg)
    V = cfg.padded_vocab
    n_units = cfg.n_layers // len(cfg.block_pattern)
    is_hybrid = cfg.family == "hybrid" and cfg.attn_every > 0

    def init(key):
        kE, kB, kS, kH, kV = jax.random.split(key, 5)
        params = {
            "embed": embedding_init(kE, V, cfg.d_model, dtype),
            "final_norm": norm_init(cfg.d_model, dtype, cfg.norm_kind),
        }
        if is_hybrid:
            G, k, R = hybrid_layout(cfg)
            kg, kt = jax.random.split(kB)
            params["groups"] = jax.vmap(
                lambda ks: jax.vmap(lambda k2: _unit_init(k2, cfg, dtype))(ks)
            )(jax.random.split(kg, G * k).reshape(G, k, 2))
            if R:
                params["tail"] = jax.vmap(lambda k2: _unit_init(k2, cfg, dtype))(
                    jax.random.split(kt, R)
                )
            params["shared"] = _shared_attn_init(kS, cfg, dtype)
        else:
            params["blocks"] = jax.vmap(lambda k2: _unit_init(k2, cfg, dtype))(
                jax.random.split(kB, n_units)
            )
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(kH, cfg.d_model, V, dtype)
        if cfg.family == "vlm":
            params["vision_proj"] = dense_init(kV, cfg.vision_dim, cfg.d_model, dtype)
        return params

    def embed_inputs(params, batch):
        x = embed(params["embed"], batch["tokens"])
        if cfg.family == "vlm":
            vis = dense(params["vision_proj"], batch["vision_embed"].astype(dtype))
            x = jnp.concatenate([vis, x], axis=1)
        return x

    def head(params, x):
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            return unembed(params["embed"], x)
        return dense(params["lm_head"], x).astype(jnp.float32)

    def backbone(params, x, positions):
        if is_hybrid:
            x, _ = _hybrid_backbone(params, x, positions, cfg, remat)
            return x, jnp.zeros((), jnp.float32)
        return _decoder_backbone(params, x, positions, cfg, remat)

    def logits_fn(params, batch):
        x = embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        x, _ = backbone(params, x, positions)
        logits = head(params, x)
        if cfg.family == "vlm":
            logits = logits[:, batch["vision_embed"].shape[1]:]
        return logits

    def aux_fn(params, batch):
        x = embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        _, aux = backbone(params, x, positions)
        return aux

    def out_loss_fn(logits, batch):
        return _ce_loss(logits, batch)

    def loss_fn(params, batch):
        x = embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        x, aux = backbone(params, x, positions)
        if cfg.ce_chunk:
            x = apply_norm(params["final_norm"], x, cfg.norm_eps)
            if cfg.family == "vlm":
                x = x[:, batch["vision_embed"].shape[1]:]
            mask = batch.get("loss_mask")
            if mask is None:
                mask = jnp.ones(batch["targets"].shape, jnp.float32)
            w = params["embed"]["table"] if cfg.tie_embeddings else params["lm_head"]["w"]
            ce = _chunked_ce(x, w, batch["targets"], mask, cfg.ce_chunk,
                             vocab_major=cfg.tie_embeddings)
            return ce + cfg.router_aux_weight * aux
        logits = head(params, x)
        if cfg.family == "vlm":
            logits = logits[:, batch["vision_embed"].shape[1]:]
        return _ce_loss(logits, batch) + cfg.router_aux_weight * aux

    def init_cache(batch_size, max_len):
        if is_hybrid:
            G, k, R = hybrid_layout(cfg)
            unit = _unit_cache_zeros(cfg, batch_size, max_len, dtype)
            attn_unit = _unit_cache_zeros(
                cfg.replace(block_pattern=("attn",)), batch_size, max_len, dtype
            )["b0_attn"]
            return {
                "groups_mamba": _stack(_stack(unit, k), G),
                "groups_attn": _stack(attn_unit, G),
                "tail": _stack(unit, R) if R else None,
            }
        unit = _unit_cache_zeros(cfg, batch_size, max_len, dtype)
        return _stack(unit, n_units)

    def prefill(params, batch, max_len):
        x = embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        if is_hybrid:
            x, caches = _hybrid_backbone(
                params, x, positions, cfg, remat, produce_cache=True, max_len=max_len
            )
        else:
            x, _, caches = _decoder_backbone_prefill(params, x, positions, cfg, max_len)
        logits = head(params, x[:, -1:])
        return logits, caches

    def decode_step(params, token, t, caches):
        x = embed(params["embed"], token)
        if is_hybrid:
            x, new_caches = _hybrid_decode(params, x, t, caches, cfg)
        else:
            x, new_caches = _decoder_backbone_decode(params, x, t, caches, cfg)
        return head(params, x), new_caches

    # -------------------------- continuous-batching / paged serving paths --
    # Gated to plain decoder stacks (one attn/moe block per scanned unit,
    # no vision prefix): per-slot decode positions and the shared page pool
    # only make sense where every layer's cache is a KVCache.
    supports_serving = (
        not is_hybrid
        and cfg.family != "vlm"
        and len(cfg.block_pattern) == 1
        and cfg.block_pattern[0] in ("attn", "moe")
    )
    decode_step_ragged = init_cache_ragged = None
    decode_step_paged = init_cache_paged = prefill_paged = None
    if supports_serving:
        from . import kv_paged as kvp

        bname = f"b0_{cfg.block_pattern[0]}"

        def init_cache_ragged(batch_size, max_len):
            unit = _unit_cache_zeros(cfg, batch_size, max_len, dtype,
                                     ragged=True)
            return _stack(unit, n_units)

        def decode_step_ragged(params, token, t, caches, active=None):
            x = embed(params["embed"], token)

            def body(xx, xs):
                unit, c = xs
                xx, nc = _unit_decode_ragged(unit, xx, t, c, cfg, active)
                return xx, nc

            x, new_caches = jax.lax.scan(body, x, (params["blocks"], caches))
            return head(params, x), new_caches

        def init_cache_paged(batch_size, max_len, n_pages, page_size=128):
            return kvp.init_paged_cache(cfg, n_units, batch_size, max_len,
                                        n_pages, dtype, page_size)

        def prefill_paged(params, batch, cache, slot):
            """Admit one prompt (batch["tokens"]: (1, S)) into ``slot``:
            run the dense prefill, map pages for the slot, and scatter the
            per-layer K/V into the pool in logical order. The slot's table
            row must be unmapped (released). Returns (last-token logits,
            cache)."""
            S = batch["tokens"].shape[1]
            logits, dcaches = prefill(params, batch, S)
            kv = dcaches[bname]                  # k: (L, 1, W, KV, hd)
            W = kv.k.shape[2]
            B = cache.page_table.shape[0]
            admit = jnp.arange(B) == slot
            lengths = jnp.where(admit, S, 0)
            cache = kvp.alloc_prefill(cache, lengths, admit,
                                      window=cfg.sliding_window)
            row = cache.page_table[slot][None]   # (1, max_pages)
            # rolling slot of logical position i is i % W; positions below
            # the live window alias newer ones but land on unmapped logical
            # pages (routed to the null page), so the gather is safe
            idx = jnp.arange(S) % W
            kl, vl = kv.k[:, :, idx], kv.v[:, :, idx]     # (L, 1, S, KV, hd)
            ln = jnp.full((1,), S, jnp.int32)
            kps, vps = jax.vmap(
                lambda kp, vp, k1, v1: kvp.write_prefill_kv(
                    kp, vp, row, k1, v1, ln)
            )(cache.k_pool, cache.v_pool, kl, vl)
            return logits, cache._replace(k_pool=kps, v_pool=vps)

        def decode_step_paged(params, token, cache, active=None):
            if active is None:
                active = jnp.ones((token.shape[0],), bool)
            cache = kvp.alloc_decode_page(cache, active)
            x = embed(params["embed"], token)

            def body(xx, xs):
                unit, kp, vp = xs
                p = unit[bname]
                h = apply_norm(p["norm1"], xx, cfg.norm_eps)
                a, (kp, vp) = kvp.paged_decode_attend(
                    p["attn"], h, (kp, vp), cache.page_table, cache.seq_len,
                    cfg, active=active)
                xx = xx + a
                h = apply_norm(p["norm2"], xx, cfg.norm_eps)
                if cfg.block_pattern[0] == "attn":
                    xx = xx + apply_mlp(p["mlp"], h)
                else:
                    mo, _ = apply_moe(p["moe"], h, cfg)
                    xx = xx + mo
                return xx, (kp, vp)

            x, (kps, vps) = jax.lax.scan(
                body, x, (params["blocks"], cache.k_pool, cache.v_pool))
            cache = cache._replace(k_pool=kps, v_pool=vps)
            cache = kvp.advance_and_free(cache, active,
                                         window=cfg.sliding_window)
            return head(params, x), cache

    return ModelApi(cfg, init, loss_fn, logits_fn, out_loss_fn, prefill,
                    decode_step, init_cache,
                    decode_step_ragged=decode_step_ragged,
                    init_cache_ragged=init_cache_ragged,
                    decode_step_paged=decode_step_paged,
                    init_cache_paged=init_cache_paged,
                    prefill_paged=prefill_paged)


def _ce_loss(logits, batch):
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _chunked_ce(x, w, targets, mask, chunk, *, vocab_major: bool):
    """Cross-entropy without materializing the (B,S,V) logits: scan over
    vocab chunks with an online logsumexp (+ target-logit pick). The chunk
    body is rematerialized, so neither forward nor backward ever holds more
    than (B,S,chunk) activation — the §Perf pair-C optimization for 100k+
    vocabs (full-logit CE dominates HBM traffic in the HF step, where the
    loss is evaluated in the gradient, every HVP and every line-search trial).

    x: (B,S,d) hidden states; w: (V,d) if vocab_major (tied embedding) else
    (d,V) (lm head).
    """
    V = w.shape[0] if vocab_major else w.shape[1]
    assert V % chunk == 0, (V, chunk)
    nc = V // chunk
    xf = x.astype(jnp.float32)
    B, S, _ = x.shape

    @jax.checkpoint
    def body(carry, c):
        m, s, tl = carry
        if vocab_major:
            wc = jax.lax.dynamic_slice_in_dim(w, c * chunk, chunk, axis=0)
            logits = jnp.einsum("bsd,vd->bsv", xf, wc.astype(jnp.float32))
        else:
            wc = jax.lax.dynamic_slice_in_dim(w, c * chunk, chunk, axis=1)
            logits = jnp.einsum("bsd,dv->bsv", xf, wc.astype(jnp.float32))
        mc = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, mc)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1
        )
        loc = targets - c * chunk
        in_c = jnp.logical_and(loc >= 0, loc < chunk)
        tl_c = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, chunk - 1)[..., None], axis=-1
        )[..., 0]
        tl = jnp.where(in_c, tl_c, tl)
        return (m_new, s, tl), None

    init = (
        jnp.full((B, S), -1e30, jnp.float32),
        jnp.zeros((B, S), jnp.float32),
        jnp.zeros((B, S), jnp.float32),
    )
    (m, s, tl), _ = jax.lax.scan(body, init, jnp.arange(nc))
    nll = jnp.log(s) + m - tl
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------- encoder-decoder ---
def _whisper_attn_init(key, cfg, dtype):
    """Whisper's attention: biases on the query, value and output
    projections, none on the key."""
    from .attention import attn_init
    p = attn_init(key, cfg, dtype)
    for name in ("wq", "wv", "wo"):
        p[name]["b"] = jnp.zeros((p[name]["w"].shape[1],), dtype)
    return p


def _enc_unit_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    return {
        "norm1": norm_init(d, dtype, cfg.norm_kind),
        "attn": _whisper_attn_init(k1, cfg, dtype),
        "norm2": norm_init(d, dtype, cfg.norm_kind),
        "mlp": mlp_init(k2, d, cfg.d_ff, dtype, cfg.mlp_act, bias=True),
    }


def _dec_unit_init(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    return {
        "norm1": norm_init(d, dtype, cfg.norm_kind),
        "self_attn": _whisper_attn_init(k1, cfg, dtype),
        "norm2": norm_init(d, dtype, cfg.norm_kind),
        "cross_attn": _whisper_attn_init(k2, cfg, dtype),
        "norm3": norm_init(d, dtype, cfg.norm_kind),
        "mlp": mlp_init(k3, d, cfg.d_ff, dtype, cfg.mlp_act, bias=True),
    }


def whisper_sinusoids(length, channels):
    """Whisper's fixed encoder positions: sines over the first half of the
    channels, cosines over the second, timescales from 1 to 10000."""
    inc = jnp.log(10000.0) / (channels // 2 - 1)
    inv = jnp.exp(-inc * jnp.arange(channels // 2, dtype=jnp.float32))
    t = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=1)


def build_encdec_model(cfg, *, remat: bool = False) -> ModelApi:
    """Whisper (arXiv:2212.04356): a conv stem over log-mel frames (conv
    width 3 from ``n_mels`` to d_model, GELU, conv width 3 stride 2, GELU),
    fixed sinusoidal positions, a bidirectional pre-LN encoder; a pre-LN
    decoder with learned positions (``max_target_positions`` of them),
    causal self-attention and per-layer cross-attention; exact GELU MLPs;
    biases on every dense layer but the key projections; the head tied to
    the token embedding, over the unpadded vocabulary.

    Batches carry ``mel`` (B, 2·n_audio_frames, n_mels), ``tokens``,
    ``targets`` and ``loss_mask`` (B, S) with S <= max_target_positions.
    The head and the masked cross-entropy run in ``jax.named_scope
    ("lm_head")``; the cross-attention's key and value projections of the
    encoder's output run in ``jax.named_scope("attention")``, as the
    attention sublayers do."""
    dtype = dtype_of(cfg)
    V = cfg.vocab_size

    def init(key):
        kC1, kC2, kE, kP, kEnc, kDec = jax.random.split(key, 6)
        d = cfg.d_model
        return {
            "conv1": conv_init(kC1, 3, cfg.n_mels, d, dtype),
            "conv2": conv_init(kC2, 3, d, d, dtype),
            "enc_blocks": jax.vmap(lambda k: _enc_unit_init(k, cfg, dtype))(
                jax.random.split(kEnc, cfg.n_encoder_layers)
            ),
            "enc_norm": norm_init(d, dtype, cfg.norm_kind),
            "embed": embedding_init(kE, V, d, dtype),
            "dec_pos": (jax.random.normal(kP, (cfg.max_target_positions, d))
                        * 0.02).astype(dtype),
            "dec_blocks": jax.vmap(lambda k: _dec_unit_init(k, cfg, dtype))(
                jax.random.split(kDec, cfg.n_layers)
            ),
            "final_norm": norm_init(d, dtype, cfg.norm_kind),
        }

    def encode(params, mel):
        x = jax.nn.gelu(conv1d(params["conv1"], mel.astype(dtype)),
                        approximate=False)
        x = jax.nn.gelu(conv1d(params["conv2"], x, stride=2), approximate=False)
        if x.shape[1] != cfg.n_audio_frames:
            raise ValueError(f"{mel.shape[1]} mel frames give {x.shape[1]} "
                             f"encoder positions, not {cfg.n_audio_frames}")
        x = x + whisper_sinusoids(x.shape[1], cfg.d_model).astype(dtype)[None]

        def body(xx, unit):
            h = apply_norm(unit["norm1"], xx, cfg.norm_eps)
            xx = xx + encoder_attend(unit["attn"], h, cfg)
            xx = xx + apply_mlp(unit["mlp"], apply_norm(unit["norm2"], xx, cfg.norm_eps))
            return xx, None

        x, _ = jax.lax.scan(_make_remat(body, remat), x, params["enc_blocks"])
        return apply_norm(params["enc_norm"], x, cfg.norm_eps)

    @jax.named_scope("attention")
    def _cross_kv(unit, enc_out):
        KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        k = _split_heads(dense(unit["cross_attn"]["wk"], enc_out), KV, hd)
        v = _split_heads(dense(unit["cross_attn"]["wv"], enc_out), KV, hd)
        return k, v

    def decode_seq(params, tokens, enc_out, *, produce_cache=False, max_len=None):
        """The decoder's final-norm hidden states (and, for prefill, its
        caches)."""
        S = tokens.shape[1]
        if S > cfg.max_target_positions:
            raise ValueError(f"{S} decoder positions; whisper has "
                             f"{cfg.max_target_positions}")
        x = embed(params["embed"], tokens) + params["dec_pos"][:S][None]
        positions = jnp.arange(S)

        def body(xx, unit):
            h = apply_norm(unit["norm1"], xx, cfg.norm_eps)
            if produce_cache:
                a, kv = attend_full_with_cache(unit["self_attn"], h, positions, cfg, max_len)
            else:
                a, kv = attend_full(unit["self_attn"], h, positions, cfg), None
            xx = xx + a
            ck, cv = _cross_kv(unit, enc_out)
            h = apply_norm(unit["norm2"], xx, cfg.norm_eps)
            xx = xx + attend_full(unit["cross_attn"], h, positions, cfg, cross_kv=(ck, cv))
            xx = xx + apply_mlp(unit["mlp"], apply_norm(unit["norm3"], xx, cfg.norm_eps))
            return xx, ((kv, ck, cv) if produce_cache else None)

        x, caches = jax.lax.scan(_make_remat(body, remat), x, params["dec_blocks"])
        return apply_norm(params["final_norm"], x, cfg.norm_eps), caches

    @jax.named_scope("lm_head")
    def head(params, x):
        return unembed(params["embed"], x)

    def logits_fn(params, batch):
        x, _ = decode_seq(params, batch["tokens"], encode(params, batch["mel"]))
        return head(params, x)

    @jax.named_scope("lm_head")
    def out_loss_fn(logits, batch):
        return _ce_loss(logits, batch)

    def loss_fn(params, batch):
        return out_loss_fn(logits_fn(params, batch), batch)

    def init_cache(batch_size, max_len):
        KV, hd, F = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_audio_frames
        L = cfg.n_layers
        W = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        kv = KVCache(
            k=jnp.zeros((L, batch_size, W, KV, hd), dtype),
            v=jnp.zeros((L, batch_size, W, KV, hd), dtype),
            pos=jnp.full((L, W), -1, jnp.int32),
        )
        cross = (
            jnp.zeros((L, batch_size, F, KV, hd), dtype),
            jnp.zeros((L, batch_size, F, KV, hd), dtype),
        )
        return {"self": kv, "cross_k": cross[0], "cross_v": cross[1]}

    def prefill(params, batch, max_len):
        enc_out = encode(params, batch["mel"])
        x, caches = decode_seq(
            params, batch["tokens"], enc_out, produce_cache=True, max_len=max_len
        )
        kv, ck, cv = caches
        return head(params, x[:, -1:]), {"self": kv, "cross_k": ck, "cross_v": cv}

    def decode_step(params, token, t, caches):
        pos = jax.lax.dynamic_slice_in_dim(params["dec_pos"], t, 1, axis=0)
        x = embed(params["embed"], token) + pos[None]

        def body(xx, xs):
            unit, kv, ck, cv = xs
            h = apply_norm(unit["norm1"], xx, cfg.norm_eps)
            a, nkv = decode_attend(unit["self_attn"], h, t, kv, cfg)
            xx = xx + a
            h = apply_norm(unit["norm2"], xx, cfg.norm_eps)
            xx = xx + decode_cross_attend(unit["cross_attn"], h, (ck, cv), cfg)
            xx = xx + apply_mlp(unit["mlp"], apply_norm(unit["norm3"], xx, cfg.norm_eps))
            return xx, nkv

        x, nkv = jax.lax.scan(
            body, x, (params["dec_blocks"], caches["self"], caches["cross_k"], caches["cross_v"])
        )
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        return head(params, x), {"self": nkv, "cross_k": caches["cross_k"],
                                 "cross_v": caches["cross_v"]}

    return ModelApi(
        cfg, init, loss_fn, logits_fn, out_loss_fn, prefill, decode_step, init_cache
    )
