"""End-to-end LM training driver (runs on whatever devices exist).

Trains an assigned arch (full or smoke config) with the pjit train step:
synthetic token shards, prefetch, checkpoint/restart, optional gradient
compression. This is the runnable counterpart of the train_4k dry-run
cells; ``--smoke`` uses the reduced config so a few hundred steps fit on
CPU (examples/lm_split_train.py drives the ~100M-class run).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm_360m --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import ckpt as ckptlib
from repro import configs
from repro.data.synthetic import TokenShards, prefetch
from repro.launch.mesh import make_host_mesh
from repro.models.param import ShardingRules
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainConfig, TrainState, make_train_step
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compression", default="none",
                    choices=["none", "topk", "int8"])
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    mesh = make_host_mesh(model=args.model_parallel)
    rules = ShardingRules()
    tcfg = TrainConfig(
        adamw=AdamWConfig(lr=args.lr, warmup_steps=10,
                          total_steps=max(args.steps, 1)),
        remat=args.remat, compression=args.compression)

    step, state_sh, _, init_state = make_train_step(cfg, mesh, rules, tcfg)
    with mesh:
        state = init_state(jax.random.key(args.seed))

    start = 0
    if args.ckpt_dir:
        last = ckptlib.latest_step(args.ckpt_dir)
        if last is not None:
            restored, meta = ckptlib.restore(args.ckpt_dir, last, state)
            state = TrainState(*restored) if isinstance(restored, (list, tuple)) \
                else restored
            start = int(meta.get("step", last))
            print(f"restored checkpoint step {last} (resuming at {start})")

    shards = TokenShards(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch,
                         seed=args.seed)
    it = prefetch(shards.iterate(shard=0, start=start))

    losses = []
    t0 = time.time()
    with mesh:
        for i in range(start, args.steps):
            batch = next(it)
            kw = {}
            if cfg.frontend == "vision":
                kw["frontend_embed"] = jnp.zeros(
                    (args.batch, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
            if cfg.frontend == "audio":
                kw["enc_frames"] = jnp.zeros(
                    (args.batch, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
            state, metrics = step(state, {**batch, **kw})
            losses.append(float(metrics["loss"]))
            if (i + 1) % args.log_every == 0:
                dt = time.time() - t0
                print(f"step {i+1:5d} loss {losses[-1]:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"({dt/args.log_every:.2f}s/step)")
                t0 = time.time()
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                ckptlib.save(args.ckpt_dir, i + 1, state,
                             meta={"step": i + 1, "arch": cfg.name})

    if losses:
        print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    else:
        print("no steps to run (checkpoint already at target step)")
    return losses


if __name__ == "__main__":
    main()
