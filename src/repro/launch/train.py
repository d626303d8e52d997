"""End-to-end training driver.

Wires configs → mesh → sharded state → data pipeline → fault-tolerant loop
(checkpoint/restart, straggler monitor, optional majority-vote compressed
DP).  On one host it drives the local device mesh; on a cluster the same
code runs per-process under ``jax.distributed`` (the data pipeline already
slices per host).

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --reduced --steps 200 --batch 8 --seq 256 [--compressed]
"""
from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import PartitionSpec as PS

from ..configs import get_config, get_reduced
from ..distributed.checkpoint import CheckpointManager
from ..distributed.failover import FailoverConfig, FailoverRunner
from ..distributed.sharding import replicated, tree_shardings
from ..models.params import init_params
from ..models.transformer import model_defs
from ..train.data import DataConfig, synthetic_batch
from ..train.optimizer import AdamWConfig, AdamWState
from ..train.train_step import (TrainState, init_train_state,
                                make_compressed_train_step, make_train_step)
from .compile_cache import configure_compile_cache
from .mesh import make_mesh


def build_mesh(n_model: int | None = None):
    n_dev = len(jax.devices())
    n_model = n_model or (2 if n_dev % 2 == 0 and n_dev > 1 else 1)
    n_data = n_dev // n_model
    return make_mesh((n_data, n_model), ("data", "model"))


def setup(cfg, mesh, opt_cfg: AdamWConfig, compressed: bool = False,
          microbatches: int = 1, seed: int = 0, exchange: str = "packed"):
    defs = model_defs(cfg)
    shardings = tree_shardings(defs, mesh)
    params = init_params(defs, jax.random.key(seed))
    params = jax.tree.map(jax.device_put, params, shardings)
    state = init_train_state(params, compressed=compressed)
    st_shard = TrainState(
        params=shardings,
        opt=AdamWState(step=replicated(mesh), m=shardings, v=shardings),
        error_fb=shardings if compressed else None)
    if compressed:
        step_inner, data_axes = make_compressed_train_step(
            cfg, opt_cfg, mesh, exchange=exchange)
        # manual over the data axes (explicit packed-sign collectives); the
        # model axis stays under the automatic partitioner
        pspec = PS()
        bspec = PS(data_axes if len(data_axes) > 1 else data_axes[0])
        step = jax.shard_map(
            step_inner, mesh=mesh, axis_names=set(data_axes),
            in_specs=(jax.tree.map(lambda _: pspec, state),
                      {"tokens": bspec, "labels": bspec}),
            out_specs=(jax.tree.map(lambda _: pspec, state),
                       {"loss": PS(), "aux": PS(), "grad_norm": PS(),
                        "lr": PS()}),
            check_vma=False)
        step = jax.jit(step, donate_argnums=(0,))
    else:
        step = jax.jit(make_train_step(cfg, opt_cfg,
                                       microbatches=microbatches),
                       in_shardings=(st_shard, None),
                       donate_argnums=(0,))
    return state, st_shard, step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compressed", action="store_true",
                    help="majority-vote 1-bit gradient all-reduce")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = build_mesh()
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(10, args.steps // 20))
    state, st_shard, step = setup(cfg, mesh, opt_cfg,
                                  compressed=args.compressed,
                                  microbatches=args.microbatches)
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M mesh={dict(mesh.shape)} "
          f"compressed={args.compressed}")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)

    def batch_fn(s):
        return synthetic_batch(dcfg, s)

    ckpt = CheckpointManager(args.ckpt_dir)
    runner = FailoverRunner(step, ckpt,
                            FailoverConfig(checkpoint_every=args.ckpt_every))
    start = ckpt.latest_step() or 0
    if start:
        state = ckpt.restore(start, state, None)
        print(f"resumed from checkpoint step {start}")

    t0 = time.time()
    losses = []
    cur = state
    for s in range(start, args.steps):
        cur, metrics = step(cur, batch_fn(s))
        if s % args.log_every == 0 or s == args.steps - 1:
            loss = float(metrics["loss"])
            losses.append(loss)
            print(f"step {s:5d} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0):.1f}s)")
        if (s + 1) % args.ckpt_every == 0:
            ckpt.save(s + 1, cur, mesh)
    ckpt.wait()
    print(f"final loss {losses[-1]:.4f} (initial {losses[0]:.4f})")
    return 0 if losses[-1] < losses[0] else 1


if __name__ == "__main__":
    raise SystemExit(main())
