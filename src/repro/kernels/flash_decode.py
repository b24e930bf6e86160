"""Split-K flash-decode as Pallas TPU kernels (the serving hot path).

Decode-time attention is one query row per sequence against a long KV
window: the arithmetic is a (1, hd) @ (hd, W) matvec pair, so the kernel is
bandwidth-bound and the parallelism has to come from the *KV* axis, not the
query axis the training kernels tile. Both kernels here therefore
parallelize the grid over KV blocks ("split-K"): every grid cell runs an
online softmax over its slice of the window and emits a *partial*
(o, m, l) triple — o normalized within the slice, m the running row max,
l the softmax mass — and ``combine_splits`` merges the partials with the
same logsumexp algebra the PR 4 training kernels and
``models/decode_sharded.py`` already use (m* = max mᵢ, weights lᵢ·e^{mᵢ−m*}).
The combine is associative, so the same (o, m, l) contract also merges
*across shards* (the sequence-sharded decode schedule) and across page
splits.

Mask semantics ride in a precomputed f32 additive **bias** row per sequence
(``decode_bias`` / ``paged_bias``): rolling-slot validity (absolute position
stored per slot, -1 empty), per-sequence ragged ``t`` (continuous batching —
each slot in the batch may sit at a different decode position), sliding
windows, and missing pages all become 0/-1e30 entries of an O(B·W) vector.
That keeps the kernels free of positional bookkeeping — one mask definition
in jnp, shared with the oracle — and costs H× less HBM than the (B, H, W)
logits ``_sdpa`` materializes (the O(S²) problem does not exist at decode;
the O(H·W) logits + two-pass softmax traffic is what this kernel removes).

Kernels:

  * ``_fd_kernel``       — dense rolling cache. Grid (B, n_splits,
                           blocks_per_split): the innermost axis reduces
                           sequentially into VMEM scratch (the PR 4
                           m/l/acc recurrence, one row set per kv head), the
                           n_splits axis is embarrassingly parallel and each
                           split writes its own (o, m, l). GQA is handled by
                           shaping q as (B, KV, G, hd) — all G query heads
                           of one kv head share that head's K/V tile.
  * ``_fd_paged_kernel`` — paged cache. Grid (B, max_pages) with the
                           page table as a *scalar-prefetch* operand: the
                           K/V BlockSpec index maps dereference
                           ``page_table[b, j]`` to pick the physical pool
                           page to DMA, so the kernel gathers pages without
                           ever materializing a dense per-sequence copy.
                           Each page is one split (page_size is aligned to
                           the KV block); unmapped pages (-1) clamp to page
                           0 and are masked out by the bias.

Off-TPU both kernels run in interpret mode (how this repo validates them);
the wall-clock caveat of EXPERIMENTS.md §Perf pair F applies — the honest
CPU signal is the XLA peak-memory column of ``benchmarks/decode_bench.py``.
TPU layout note: the per-split stats outputs are (..., n_splits, KV, G)
with G in the lane dimension; for small G this under-fills the 128-lane tile, but
the stats are O(B·H·n_splits) — noise next to the K/V traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ------------------------------------------------------------ mask -> bias --
def decode_bias(pos, t, *, window=None):
    """Additive f32 bias row(s) for rolling-slot decode attention.

    ``pos``: (W,) or (B, W) absolute position stored in each cache slot
    (-1 = empty); ``t``: scalar or (B,) current decode position per
    sequence. A slot is attendable iff 0 <= pos <= t and (when a sliding
    window is set) pos > t - window. Returns (B, W) (or (1, W) for shared
    scalar inputs) with 0.0 on attendable slots and NEG_INF elsewhere —
    the ONE definition of decode-mask semantics, shared by the Pallas
    kernels, the jnp oracle, and the `_sdpa` fallback path.
    """
    pos = jnp.asarray(pos)
    t = jnp.asarray(t)
    if pos.ndim == 1:
        pos = pos[None]
    tb = t[:, None] if t.ndim == 1 else t[None, None]
    valid = jnp.logical_and(pos >= 0, pos <= tb)
    if window is not None:
        valid = jnp.logical_and(valid, pos > tb - window)
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def paged_bias(page_table, seq_len, page_size, *, window=None):
    """Additive f32 bias for paged decode attention.

    Logical token i of sequence b lives at slot i % page_size of page
    i // page_size; ``page_table``: (B, max_pages) physical page ids
    (-1 = unmapped); ``seq_len``: (B,) tokens written so far (the query
    attends positions < seq_len, i.e. t = seq_len - 1 inclusive of the
    just-written token). Returns (B, max_pages * page_size).
    """
    B, maxp = page_table.shape
    pos = jnp.arange(maxp * page_size, dtype=jnp.int32)[None]        # (1, L)
    sl = seq_len[:, None]
    valid = pos < sl
    if window is not None:
        valid = jnp.logical_and(valid, pos > sl - 1 - window)
    mapped = (page_table >= 0)[:, :, None]                            # (B, maxp, 1)
    valid = jnp.logical_and(
        valid, jnp.broadcast_to(mapped, (B, maxp, page_size)).reshape(B, -1))
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


# ------------------------------------------------------------------ kernels --
# Each grid cell stages one KV block for *all* kv heads — a (blk, KV, hd)
# tile of the cache's own (…, W, KV, hd) layout, whose two minor dims are
# whole array dims as Mosaic requires — and loops over the kv heads inside.
# So the cache is read in place (no head-major copy) and every block is
# DMA'd once for all heads.
def _fd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref,
               m_scr, l_scr, acc_scr, *, scale, n_inner, n_kv):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    bias = bias_ref[...]                                     # (1, blk_k)
    for h in range(n_kv):
        q = q_ref[h]                                         # (G, hd)
        k = k_ref[:, h, :]                                   # (blk_k, hd)
        v = v_ref[:, h, :]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + bias                                     # (G, blk_k)

        m_prev = m_scr[h]                                    # (G, 1)
        l_prev = l_scr[h]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        # masked entries carry bias <= NEG_INF, so exp underflows to exact 0
        p = jnp.exp(logits - m_safe)
        alpha = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF,
                                  m_prev - m_safe))
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[h] = m_new
        l_scr[h] = l_new

    @pl.when(i == n_inner - 1)
    def _finish():
        l_fin = l_scr[...]                                   # (KV, G, 1)
        norm = jnp.where(l_fin <= 0.0, 1.0, l_fin)
        o_ref[...] = (acc_scr[...] / norm).astype(o_ref.dtype)
        m_ref[...] = m_scr[...][..., 0]
        l_ref[...] = l_fin[..., 0]


def _fd_paged_kernel(tbl_ref, q_ref, k_ref, v_ref, bias_ref,
                     o_ref, m_ref, l_ref, *, scale, n_kv):
    # one page == one split: single-shot softmax, no scratch recurrence
    bias = bias_ref[...]                                     # (1, ps)
    for h in range(n_kv):
        q = q_ref[h]                                         # (G, hd)
        k = k_ref[:, h, :]                                   # (ps, hd)
        v = v_ref[:, h, :]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + bias
        m = jnp.max(logits, axis=1, keepdims=True)           # (G, 1)
        m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
        p = jnp.exp(logits - m_safe)
        l = jnp.sum(p, axis=1, keepdims=True)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / jnp.where(l <= 0.0, 1.0, l)
        o_ref[h] = o.astype(o_ref.dtype)
        m_ref[h] = m[:, 0]
        l_ref[h] = l[:, 0]


# ------------------------------------------------------------ split combine --
def combine_splits(o, m, l):
    """Merge per-split partials with logsumexp algebra.

    o: (B, KV, S, G, hd) per-split normalized outputs, m/l: (B, KV, S, G)
    running max / softmax mass per split (axis 2 = splits). Returns
    (o: (B, H, hd), m: (B, H), l: (B, H)) with H = KV*G (head h = kv*G + g,
    the repo's GQA grouping) — global stats so the result can be merged
    AGAIN across shards with the same algebra (decode_sharded.py).
    Fully-masked splits carry (m, l) = (NEG_INF, 0) and contribute nothing.
    """
    B, KV, S, G, hd = o.shape
    m_glob = jnp.max(m, axis=2)                              # (B, KV, G)
    m_safe = jnp.where(m_glob <= NEG_INF / 2, 0.0, m_glob)
    w = l * jnp.exp(m - m_safe[:, :, None])                  # (B, KV, S, G)
    l_glob = jnp.sum(w, axis=2)
    o_glob = jnp.sum(o * w[..., None], axis=2) / jnp.maximum(
        l_glob, 1e-20)[..., None]
    return (o_glob.reshape(B, KV * G, hd),
            jnp.where(m_glob <= NEG_INF / 2, NEG_INF, m_glob).reshape(B, KV * G),
            l_glob.reshape(B, KV * G))


def _pick_splits(n_blocks, n_splits):
    """Largest divisor of n_blocks that is <= n_splits (static)."""
    s = max(1, min(n_splits, n_blocks))
    while n_blocks % s:
        s -= 1
    return s


# ----------------------------------------------------------------- wrappers --
def flash_decode(q, k, v, bias, *, scale=None, blk_k=128, n_splits=8,
                 interpret=False, return_stats=False):
    """Dense split-K flash decode.

    q: (B, H, hd) one query row per sequence; k/v: (B, W, KV, hd) rolling
    cache; bias: (B, W) or (1, W) additive mask row (``decode_bias``).
    W is padded to the KV block with NEG_INF bias; the block count is split
    into the largest divisor <= ``n_splits`` parallel grid cells. Returns
    (B, H, hd), or (o, m, l) with (B, H) global stats when
    ``return_stats`` (the cross-shard merge contract).
    """
    B, H, hd = q.shape
    W, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = float(scale if scale is not None else 1.0 / (hd ** 0.5))
    blk_k = min(blk_k, max(W, 8))
    Wp = -(-W // blk_k) * blk_k
    if Wp != W:
        pad = ((0, 0), (0, Wp - W), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        bias = jnp.pad(bias, ((0, 0), (0, Wp - W)), constant_values=NEG_INF)
    if bias.shape[0] != B:
        bias = jnp.broadcast_to(bias, (B, Wp))
    nk = Wp // blk_k
    ns = _pick_splits(nk, n_splits)
    n_inner = nk // ns
    qg = q.reshape(B, KV, G, hd)

    kernel = functools.partial(_fd_kernel, scale=scale, n_inner=n_inner,
                               n_kv=KV)
    o, m, l = pl.pallas_call(
        kernel,
        name="flash_decode",
        grid=(B, ns, n_inner),
        in_specs=[
            pl.BlockSpec((None, KV, G, hd), lambda b, s, i: (b, 0, 0, 0)),
            pl.BlockSpec((None, blk_k, KV, hd),
                         lambda b, s, i: (b, s * n_inner + i, 0, 0)),
            pl.BlockSpec((None, blk_k, KV, hd),
                         lambda b, s, i: (b, s * n_inner + i, 0, 0)),
            pl.BlockSpec((None, 1, blk_k),
                         lambda b, s, i: (b, 0, s * n_inner + i)),
        ],
        out_specs=_split_out_specs(KV, G, hd, lambda b, s, i: (b, s)),
        out_shape=_split_out_shapes(B, ns, KV, G, hd, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, bias[:, None])
    return _combine(o, m, l, q.dtype, return_stats)


def _split_out_specs(KV, G, hd, cell):
    """(o, m, l) partial blocks, one per (sequence, split): all kv heads."""
    return (
        pl.BlockSpec((None, None, KV, G, hd), lambda *g: (*cell(*g), 0, 0, 0)),
        pl.BlockSpec((None, None, KV, G), lambda *g: (*cell(*g), 0, 0)),
        pl.BlockSpec((None, None, KV, G), lambda *g: (*cell(*g), 0, 0)),
    )


def _split_out_shapes(B, ns, KV, G, hd, dtype):
    return (
        jax.ShapeDtypeStruct((B, ns, KV, G, hd), dtype),
        jax.ShapeDtypeStruct((B, ns, KV, G), jnp.float32),
        jax.ShapeDtypeStruct((B, ns, KV, G), jnp.float32),
    )


def _combine(o, m, l, dtype, return_stats):
    """Split-major kernel partials -> combine_splits' (B, KV, S, ...) order."""
    og, mg, lg = combine_splits(o.astype(jnp.float32).transpose(0, 2, 1, 3, 4),
                                m.transpose(0, 2, 1, 3), l.transpose(0, 2, 1, 3))
    og = og.astype(dtype)
    return (og, mg, lg) if return_stats else og


def flash_decode_paged(q, k_pool, v_pool, page_table, bias, *, scale=None,
                       interpret=False, return_stats=False):
    """Paged split-K flash decode (one page = one split).

    q: (B, H, hd); k_pool/v_pool: (P, page_size, KV, hd) — the *shared* page
    pool; page_table: (B, max_pages) int32 physical page per logical page
    (-1 unmapped); bias: (B, max_pages * page_size) (``paged_bias``). The
    page table is a scalar-prefetch operand: the K/V index maps dereference
    it to choose the pool page each grid cell DMAs, so unmapped logical
    pages cost a clamped re-read of page 0 (fully bias-masked) and no dense
    gather ever exists.
    """
    B, H, hd = q.shape
    P, ps, KV, _ = k_pool.shape
    maxp = page_table.shape[1]
    G = H // KV
    scale = float(scale if scale is not None else 1.0 / (hd ** 0.5))
    qg = q.reshape(B, KV, G, hd)

    kernel = functools.partial(_fd_paged_kernel, scale=scale, n_kv=KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((None, KV, G, hd), lambda b, j, tbl: (b, 0, 0, 0)),
            pl.BlockSpec((None, ps, KV, hd),
                         lambda b, j, tbl: (jnp.maximum(tbl[b, j], 0), 0, 0, 0)),
            pl.BlockSpec((None, ps, KV, hd),
                         lambda b, j, tbl: (jnp.maximum(tbl[b, j], 0), 0, 0, 0)),
            pl.BlockSpec((None, 1, ps), lambda b, j, tbl: (b, 0, j)),
        ],
        out_specs=_split_out_specs(KV, G, hd, lambda b, j, tbl: (b, j)),
    )
    o, m, l = pl.pallas_call(
        kernel,
        name="flash_decode_paged",
        grid_spec=grid_spec,
        out_shape=_split_out_shapes(B, maxp, KV, G, hd, q.dtype),
        interpret=interpret,
    )(page_table, qg, k_pool, v_pool, bias[:, None])
    return _combine(o, m, l, q.dtype, return_stats)
