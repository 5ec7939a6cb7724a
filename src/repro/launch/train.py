"""End-to-end training driver.

Runs the same ``train_step`` the dry-run lowers, on real devices (the CPU
smoke path uses reduced configs; on a TPU slice the production configs and
``make_production_mesh`` apply unchanged).

Example (CPU, ~100M model, a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_1_5b --smoke \
      --steps 300 --batch 8 --seq 256 --d-model 384 --layers 6
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import synthetic_token_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models.config import ShapeConfig


def build_cfg(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    overrides = {}
    if args.layers:
        overrides["num_layers"] = args.layers
    if args.d_model:
        overrides["d_model"] = args.d_model
        overrides["head_dim"] = max(args.d_model // cfg.num_heads, 8)
    if args.vocab:
        overrides["vocab_size"] = args.vocab
    return cfg.replace(**overrides) if overrides else cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1_5b")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = build_cfg(args)
    shape = ShapeConfig("cli", args.seq, args.batch, "train",
                        microbatches=args.microbatches)
    step_fn, model, opt = make_train_step(cfg, shape)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    opt_state = opt.init(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.batch}x{args.seq} steps={args.steps}")

    losses = []
    t0 = time.time()
    for i, batch in enumerate(
        synthetic_token_batches(
            cfg, args.batch, args.seq, steps=args.steps, seed=args.seed
        )
    ):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            print(f"step {i:5d}  loss {loss:.4f}  gnorm "
                  f"{float(metrics['grad_norm']):.3f}  {dt:.1f}s")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, args.steps, params)
        print("saved", args.checkpoint)
    assert losses[-1] < losses[0], "loss did not decrease"
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
