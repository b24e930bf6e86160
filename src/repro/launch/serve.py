"""Serving drivers: batch-at-once greedy decode and continuous batching.

``serve`` prefills a batch of prompts together and greedy-decodes them in
lockstep (batch-at-once — every slot finishes before new work starts). The
decode jit donates the cache and token buffers (``donate_argnums``) so XLA
updates the KV cache in place instead of round-tripping it through HBM each
token, and generated tokens land in a preallocated (B, gen_len) host buffer.

``serve_continuous`` is the production pattern the tentpole builds: a
slot-based scheduler over the paged KV cache (models/kv_paged.py). Requests
arrive on a step clock (e.g. a Poisson trace), get admitted into freed
slots as capacity allows (``prefill_paged`` writes their pages directly),
decode advances every live slot in one fixed-shape jitted step (occupancy
mask, per-slot seq_len), and finished sequences retire via
``release_slots`` — so short requests never wait on long ones and HBM is
~live-tokens, not batch × max_len.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --batch-size 4 --prompt-len 16 --gen-len 16 [--continuous]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..data import lm_batch
from ..models import build_model
from ..models.kv_paged import pages_needed, release_slots
from .cache import enable_compile_cache


def serve(arch: str, *, smoke=True, batch_size=4, prompt_len=16, gen_len=16,
          telemetry=None, log_fn=print):
    """Batch-at-once greedy decode. Returns (tokens, stats) — stats carries
    the same timing the log line prints (prefill_s, decode_s, tok/s), and
    when a ``repro.obs.telemetry.Telemetry`` sink is passed the phases are
    ALSO emitted as telemetry spans (``log_fn`` keeps working either way —
    the sink is structured output, not a replacement for the log)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = lm_batch(jax.random.PRNGKey(1), cfg, batch_size, prompt_len + 1)
    prompt = dict(batch)
    prompt["tokens"] = batch["tokens"][:, :prompt_len]
    max_len = prompt_len + gen_len + (cfg.n_vision_tokens if cfg.family == "vlm" else 0)

    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len))
    # donate the cache buffers: the cache updates in place instead of
    # allocating a fresh (B, W, KV, hd) per layer per token (the int32
    # token buffer has no same-shape output to alias, so it stays)
    decode = jax.jit(model.decode_step, donate_argnums=(3,))

    out = np.zeros((batch_size, gen_len), np.int32)
    t_start = time.time()
    logits, cache = prefill(params, prompt)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_mid = time.time()
    t_prefill = t_mid - t_start

    offset = cfg.n_vision_tokens if cfg.family == "vlm" else 0
    out[:, 0] = np.asarray(tok[:, 0])
    for i in range(gen_len - 1):
        t = jnp.asarray(prompt_len + offset + i, jnp.int32)
        logits, cache = decode(params, tok, t, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out[:, i + 1] = np.asarray(tok[:, 0])
    jax.block_until_ready(tok)
    t_end = time.time()
    t_decode = t_end - t_mid
    n_tok = batch_size * gen_len            # every generated token counts
    stats = {"prefill_s": t_prefill, "decode_s": t_decode,
             "n_tok": n_tok,
             "tok_per_s": n_tok / max(t_prefill + t_decode, 1e-9),
             "tok_per_s_decode":
                 batch_size * (gen_len - 1) / max(t_decode, 1e-9)}
    if telemetry is not None:
        telemetry.emit({"ev": "span", "name": "prefill", "t0": t_start,
                        "t1": t_mid, "batch": batch_size,
                        "prompt_len": prompt_len})
        telemetry.emit({"ev": "span", "name": "decode", "t0": t_mid,
                        "t1": t_end, "batch": batch_size,
                        "gen_len": gen_len})
        telemetry.counter("tok_per_s", stats["tok_per_s"])
    log_fn(f"prefill {prompt_len} toks x{batch_size}: {t_prefill:.3f}s; "
           f"decode {gen_len - 1} steps: {t_decode:.3f}s "
           f"({stats['tok_per_s']:.1f} tok/s end-to-end, "
           f"{stats['tok_per_s_decode']:.1f} tok/s decode)")
    return out, stats


def serve_continuous(arch: str, *, smoke=True, batch_size=4, n_requests=8,
                     prompt_len=16, gen_len=16, arrival_steps=None,
                     gen_lens=None, prompts=None, page_size=8, n_pages=None,
                     gang=False, telemetry=None, log_fn=print):
    """Continuous batching over the paged cache.

    ``arrival_steps``: per-request decode-step at which it may be admitted
    (None = all at step 0 — e.g. a precomputed Poisson trace). ``prompts``:
    optional list of (1, prompt_len) token arrays (default: rows of the
    same ``lm_batch`` draw ``serve`` uses, so outputs are comparable).
    ``gen_lens``: per-request generation lengths (ragged; default
    ``gen_len`` each). ``gang=True`` degrades the scheduler to
    batch-at-once — admission waits until *every* slot is free, so short
    requests hold their slot idle while long ones finish (the baseline the
    decode bench compares against; same driver, same step clock). Returns
    (tokens: (n_requests, gen_len) host array, rows past a request's own
    ``gen_lens`` entry zero-filled, stats dict).
    """
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    if model.decode_step_paged is None:
        raise ValueError(f"{arch}: continuous batching needs a plain "
                         "decoder stack (dense/moe family)")
    params = model.init(jax.random.PRNGKey(0))
    if prompts is None:
        batch = lm_batch(jax.random.PRNGKey(1), cfg, n_requests, prompt_len + 1)
        prompts = [batch["tokens"][r:r + 1, :prompt_len]
                   for r in range(n_requests)]
    if arrival_steps is None:
        arrival_steps = [0] * n_requests
    if gen_lens is None:
        gen_lens = [gen_len] * n_requests
    assert max(gen_lens) <= gen_len, (gen_lens, gen_len)
    max_len = prompt_len + gen_len
    if n_pages is None:
        # live pages per slot + one step of slack, + the null page
        per_slot = pages_needed(max_len, page_size, cfg.sliding_window) + 1
        n_pages = 1 + batch_size * per_slot
    B = batch_size
    cache = model.init_cache_paged(B, max_len, n_pages, page_size)

    prefill_j = jax.jit(model.prefill_paged, donate_argnums=(2,))
    decode_j = jax.jit(model.decode_step_paged, donate_argnums=(2,))
    release_j = jax.jit(release_slots, donate_argnums=(0,))
    need_pages = pages_needed(prompt_len, page_size, cfg.sliding_window)

    out = np.zeros((n_requests, gen_len), np.int32)
    slot_req = [-1] * B                     # request id per slot (-1 free)
    n_gen = [0] * B
    tok = jnp.zeros((B, 1), jnp.int32)
    next_req, done, step = 0, 0, 0
    # Per-request telemetry bookkeeping: admit wall-clock + time-to-first-
    # token (prefill returns the first token, so TTFT closes with it).
    req_t0 = [None] * n_requests
    req_ttft = [None] * n_requests
    t0 = time.time()
    while done < n_requests:
        # ---- admit arrived requests into free slots (capacity permitting);
        # gang mode (batch-at-once baseline) waits for the whole batch to
        # drain before admitting the next wave
        admit = range(0) if gang and any(s >= 0 for s in slot_req) else range(B)
        for b in admit:
            if slot_req[b] >= 0 or next_req >= n_requests:
                continue
            if arrival_steps[next_req] > step:
                break                       # in-order admission
            if int(cache.n_free) < need_pages + 1:
                break                       # backpressure: wait for frees
            pbatch = {"tokens": prompts[next_req]}
            req_t0[next_req] = time.time()
            logits, cache = prefill_j(params, pbatch, cache, jnp.asarray(b))
            t0k = jnp.argmax(logits[0, -1]).astype(jnp.int32)
            tok = tok.at[b, 0].set(t0k)
            slot_req[b], n_gen[b] = next_req, 1
            out[next_req, 0] = int(t0k)     # host sync: first token is real
            req_ttft[next_req] = time.time() - req_t0[next_req]
            next_req += 1
        if telemetry is not None:
            # Scheduler-state counters, once per step clock tick: requests
            # arrived but not yet admitted, and the page-pool headroom the
            # admission backpressure tests against.
            queued = sum(1 for r in range(next_req, n_requests)
                         if arrival_steps[r] <= step)
            telemetry.counter("queue_depth", queued)
            telemetry.counter("pages_free", int(cache.n_free))
        active_h = [slot_req[b] >= 0 for b in range(B)]
        if not any(active_h):
            step += 1                       # idle: nothing arrived yet
            continue
        # ---- one fixed-shape decode step over every slot
        logits, cache = decode_j(params, tok, cache,
                                 jnp.asarray(active_h))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        retire = []
        for b in range(B):
            if slot_req[b] < 0:
                continue
            out[slot_req[b], n_gen[b]] = int(tok[b, 0])
            n_gen[b] += 1
            if n_gen[b] == gen_lens[slot_req[b]]:   # finished: free slot + pages
                rid = slot_req[b]
                if telemetry is not None:
                    telemetry.emit({
                        "ev": "span", "name": "request", "req": rid,
                        "slot": b, "t0": req_t0[rid], "t1": time.time(),
                        "ttft_s": req_ttft[rid], "n_tok": gen_lens[rid]})
                retire.append(b)
                done += 1
                slot_req[b] = -1
        if retire:
            mask = np.zeros((B,), bool)
            mask[retire] = True
            cache = release_j(cache, jnp.asarray(mask))
        step += 1
    jax.block_until_ready(tok)
    wall = time.time() - t0
    n_tok = sum(gen_lens)
    stats = {"wall_s": wall, "steps": step, "n_tok": n_tok,
             "tok_per_s": n_tok / max(wall, 1e-9),
             "tok_per_step": n_tok / max(step, 1),
             "n_pages": n_pages, "page_size": page_size}
    log_fn(f"continuous: {n_requests} reqs x {gen_len} toks on {B} slots, "
           f"{step} steps, {wall:.3f}s ({stats['tok_per_s']:.1f} tok/s)")
    return out, stats


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="slot-scheduled continuous batching (paged cache)")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--telemetry-dir", default=None,
                    help="write serving telemetry (request spans with "
                         "TTFT, queue-depth / page-pool counters) as "
                         "events-p0.jsonl + merged trace.json; inspect "
                         "with python -m repro.obs.report DIR")
    args = ap.parse_args()
    sink = None
    if args.telemetry_dir:
        from ..obs import telemetry as telemetry_mod
        sink = telemetry_mod.Telemetry(
            args.telemetry_dir,
            meta=dict(kind="serve", arch=args.arch,
                      continuous=args.continuous))
    if args.continuous:
        gen, _ = serve_continuous(
            args.arch, smoke=args.smoke, batch_size=args.batch_size,
            n_requests=args.n_requests, prompt_len=args.prompt_len,
            gen_len=args.gen_len, telemetry=sink)
    else:
        gen, _ = serve(args.arch, smoke=args.smoke,
                       batch_size=args.batch_size,
                       prompt_len=args.prompt_len, gen_len=args.gen_len,
                       telemetry=sink)
    if sink is not None:
        sink.close()
        from ..obs import trace as trace_mod
        trace_mod.merge_dir(args.telemetry_dir)
    print("generated token ids (first row):", gen[0].tolist())


if __name__ == "__main__":
    main()
