"""End-to-end training driver.

CPU-scale by default (reduced config, a few hundred steps on the synthetic
pipeline); pass --full to run an assigned config unchanged (requires real
accelerators).  Demonstrates: config system -> mesh -> sharded state ->
fault-tolerant loop -> checkpointing, with optional sketched gradient
compression (the paper's technique as a first-class training feature).

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
      --steps 200 --batch 8 --seq 128

Sketched gradient compression (docs/TRAINING.md) is one flag away:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b \
      --steps 60 --batch 8 --grad-compress 8

which builds a 1-D "data" mesh over every device, plans the per-layer
raw-vs-sketch decisions (plan.plan_train_compression, table printed at
startup), and trains through make_dp_compressed_step — the DP all-reduce
pays r·(m+n) words per weight matrix instead of m·n (Theorem 2 regime 1:
Omega is regenerated, never communicated).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.data.pipeline import DataConfig
from repro.models import get_api
from repro.models.common import NULL_CTX
from repro.train.loop import train_loop
from repro.train.step import init_state, make_dp_compressed_step, \
    make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--full", action="store_true",
                    help="use the full (assigned) config, not the reduced")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grad-compress", type=int, default=0, metavar="RANK",
                    help="sketched gradient compression at this rank over a "
                         "1-D DP mesh of all devices (0 = off; "
                         "docs/TRAINING.md)")
    ap.add_argument("--grad-backend", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="local GEMM bodies of the compressed exchange "
                         "(kernels/local.py; auto = pallas on TPU)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    api = get_api(cfg)
    run = RunConfig(steps=args.steps, learning_rate=args.lr,
                    checkpoint_every=args.ckpt_every,
                    checkpoint_dir=args.ckpt_dir, seed=args.seed,
                    remat=True, grad_compress_rank=args.grad_compress,
                    grad_compress_backend=args.grad_backend)

    data_cfg = DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
        frontend=("vision" if cfg.family == "vlm"
                  else "audio" if cfg.family == "encdec" else "none"),
        frontend_dim=cfg.frontend_dim,
        num_frontend_tokens=cfg.num_frontend_tokens,
        enc_seq=cfg.enc_seq if cfg.family == "encdec" else 0,
        d_model=cfg.d_model)

    print(f"[train] arch={cfg.name} family={cfg.family} "
          f"steps={run.steps} batch={args.batch} seq={args.seq}")
    if args.grad_compress:
        # planner-priced sketched DP exchange over a 1-D "data" mesh
        from jax.sharding import Mesh
        from repro.plan import explain_train_compression, \
            plan_train_compression
        devices = jax.devices()
        if args.batch % len(devices):
            raise SystemExit(f"--batch {args.batch} must divide over "
                             f"{len(devices)} DP workers")
        mesh = Mesh(np.asarray(devices), ("data",))
        shapes = jax.eval_shape(lambda k: api.init(k, cfg),
                                jax.random.key(run.seed))
        plan = plan_train_compression(
            shapes, rank=run.grad_compress_rank, P=len(devices),
            backend=None if args.grad_backend == "auto"
            else args.grad_backend)
        print(explain_train_compression(plan))
        state = init_state(api, cfg, run, jax.random.key(run.seed),
                           world=len(devices),
                           decisions=plan.decision_tree())
        step_fn = make_dp_compressed_step(api, cfg, run, mesh,
                                          axis="data", plan=plan,
                                          backend=args.grad_backend)
    else:
        state = init_state(api, cfg, run, jax.random.key(run.seed))
        step_fn = jax.jit(make_train_step(api, cfg, run, NULL_CTX))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    print(f"[train] params: {n_params/1e6:.2f}M")

    t0 = time.time()
    result = train_loop(step_fn, state, data_cfg, run)
    dt = time.time() - t0

    first = np.mean(result.losses[:10])
    last = np.mean(result.losses[-10:])
    print(f"[train] done in {dt:.1f}s; loss {first:.4f} -> {last:.4f} "
          f"({len(result.losses)} steps, {result.restarts} restarts, "
          f"{len(result.checkpoints)} checkpoints)")
    assert last < first, "loss did not decrease"
    return result


if __name__ == "__main__":
    main()
