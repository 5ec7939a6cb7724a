"""Batched serving driver: continuous-batching loop over prefill + decode.

Requests arrive with different prompt lengths; batching is delegated to the
serving tier's :class:`~repro.runtime.serving.SlotQueue` — the same bucketed
slot queue the request-driven :class:`~repro.runtime.serving.RegionServer`
uses — so the repo has exactly one batching implementation.  Each drained
slot is left-padded to its bucket, prefilled, then decoded greedily until
max-tokens; rows land back at their original request index.

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_1_5b --smoke \
      --requests 6 --max-new 12
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.runtime.serving import SlotQueue


def make_requests(cfg, n, seed=0, lo=4, hi=24):
    rng = np.random.RandomState(seed)
    lens = rng.randint(lo, hi, size=n)
    return [rng.randint(1, cfg.vocab_size, size=L).astype(np.int32) for L in lens]


def pad_batch(cfg, prompts, bucket):
    B = len(prompts)
    toks = np.zeros((B, bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, -len(p):] = p  # left-pad so decode continues from the end
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.num_patches:
        batch["patches"] = jnp.zeros((B, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros((B, cfg.num_frames, cfg.d_model), jnp.bfloat16)
    return batch


def run_slot(cfg, prefill_fn, serve_fn, params, prompts, bucket, max_new):
    """Prefill one drained slot and decode it greedily.

    Returns ``(gen, logits, t_prefill, t_decode)`` where ``gen`` holds the
    ``(len(prompts), max_new)`` generated token ids.
    """
    batch = pad_batch(cfg, prompts, bucket)
    t0 = time.time()
    logits, cache = prefill_fn(params, batch)
    next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    t_prefill = time.time() - t0

    outs = [np.asarray(next_tok)[:, 0]]
    t0 = time.time()
    for _ in range(max_new - 1):
        tok, logits, cache = serve_fn(params, cache, {"token": next_tok})
        next_tok = tok[:, None]
        outs.append(np.asarray(tok))
    t_decode = time.time() - t0
    return np.stack(outs, axis=1), logits, t_prefill, t_decode


def build_engine(cfg, bucket, max_new):
    """``(model, prefill_fn, serve_fn)``: the jitted prefill and decode steps.

    The cache is sized for the full generation so no decode write clamps.
    """
    prefill_fn, model = make_prefill_step(cfg, cache_len=bucket + max_new)
    serve_fn, _ = make_serve_step(cfg)
    return (model, jax.jit(prefill_fn),
            jax.jit(serve_fn, donate_argnums=(1,)))


def serve_prompts(cfg, prefill_fn, serve_fn, params, prompts, *, bucket,
                  max_new, max_batch):
    """Batch ``prompts`` through the :class:`SlotQueue` and decode each slot.

    Returns ``(gen, slots)``: ``gen`` holds the ``(len(prompts), max_new)``
    generated ids at each request's index; ``slots`` lists one
    ``(idxs, logits, t_prefill, t_decode)`` per drained slot, ``logits``
    being the slot's last decode step.
    """
    queue = SlotQueue(buckets=(bucket,), max_batch=max_batch)
    for i, p in enumerate(prompts):
        queue.add(cfg.name, len(p), i)
    gen = np.zeros((len(prompts), max_new), np.int32)
    slots = []
    while len(queue):
        idxs = queue.drain(cfg.name, bucket)
        rows, logits, tp, td = run_slot(cfg, prefill_fn, serve_fn, params,
                                        [prompts[i] for i in idxs],
                                        bucket, max_new)
        gen[np.asarray(idxs)] = rows
        slots.append((idxs, logits, tp, td))
    return gen, slots


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1_5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--bucket", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model, prefill_fn, serve_fn = build_engine(cfg, args.bucket, args.max_new)
    params = model.init(jax.random.PRNGKey(args.seed))
    prompts = make_requests(cfg, args.requests, args.seed)

    gen, slots = serve_prompts(cfg, prefill_fn, serve_fn, params, prompts,
                               bucket=args.bucket, max_new=args.max_new,
                               max_batch=args.max_batch)
    for _, logits, _, _ in slots:
        assert np.isfinite(np.asarray(logits, np.float32)).all()
    t_prefill = sum(s[2] for s in slots)
    t_decode = sum(s[3] for s in slots)

    for i, p in enumerate(prompts):
        print(f"req{i}: prompt_len={len(p)} -> {gen[i, :8].tolist()}...")
    tps = args.requests * args.max_new / max(t_decode, 1e-9)
    print(f"{len(slots)} slot(s)   prefill {t_prefill:.2f}s   "
          f"decode {t_decode:.2f}s ({tps:.1f} tok/s batch-aggregate)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
