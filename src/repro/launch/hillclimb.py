"""Perf hillclimb on the three selected cells (EXPERIMENTS.md §Perf).

Cells (chosen from the baseline roofline table):
  A. qwen1.5-0.5b × train_4k   — memory-dominant with the worst
     memory/compute imbalance among dense trains; also the carrier for the
     paper-technique-derived collective optimization (majority-vote DP).
  B. olmoe-1b-7b  × train_4k   — the most collective-bound train cell
     (MoE gradient all-reduces).
  C. mamba2-130m  × train_4k   — worst useful-FLOPs roofline fraction
     (SSD scan overheads).

Each variant is a (hypothesis, config change); we re-lower on the production
single-pod mesh, re-extract the three roofline terms and record
before→after.  Run AFTER the baseline sweep:

    PYTHONPATH=src python -m repro.launch.hillclimb --out results/hillclimb.json
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import dataclasses
import json

import jax

from ..configs import get_config
from .dryrun import lower_cell
from .mesh import make_mesh, make_production_mesh

CELLS = {
    "A-qwen0.5b-train": ("qwen1.5-0.5b", "train_4k"),
    "B-olmoe-train": ("olmoe-1b-7b", "train_4k"),
    "C-mamba2-train": ("mamba2-130m", "train_4k"),
}

# hypothesis → config override (None value = baseline)
VARIANTS = {
    "A-qwen0.5b-train": [
        ("baseline (paper-faithful: f32 params, full logits, remat=full)",
         {}),
        ("H1: chunked-vocab loss removes the (tokens×vocab) f32 logits "
         "materialization → memory term drops",
         {"loss_chunk": 512}),
        ("H2: bf16 parameter storage halves param-read bytes on every "
         "layer (moments stay f32) → memory term drops further",
         {"loss_chunk": 512, "param_dtype": "bfloat16"}),
        ("H3: remat=none trades memory capacity for bandwidth: no forward "
         "recompute in backward → fewer bytes+flops IF activations fit",
         {"loss_chunk": 512, "param_dtype": "bfloat16", "remat": "none"}),
        ("H4 (beyond-paper, paper-derived): majority-vote 1-bit gradient "
         "all-reduce — pack gradient signs 32×, all-gather, bit-plane "
         "majority (SIMDRAM's TRA lifted to the collective layer), "
         "per-leaf exchanges",
         {"loss_chunk": 512, "param_dtype": "bfloat16",
          "_compressed": True, "_fused": False}),
        ("H5: same majority-vote exchange FUSED into one flat packed "
         "all-gather (kills H4's per-leaf collective latency)",
         {"loss_chunk": 512, "param_dtype": "bfloat16",
          "_compressed": True, "_fused": True}),
        ("H6a control: pure-DP 256x1 mesh, plain f32 all-reduce (the setting "
         "sign-compression actually targets)",
         {"loss_chunk": 512, "_dp_only": True}),
        ("H6b: pure-DP 256x1 mesh + fused majority-vote sign exchange → "
         "collective bytes drop vs H6a",
         {"loss_chunk": 512, "_compressed": True, "_fused": True,
          "_dp_only": True}),
        ("H7: two-phase majority exchange (all-to-all slice → local vote → "
         "all-gather result): per-device bytes independent of voter count — "
         "the scalable form of the paper-derived majority collective",
         {"loss_chunk": 512, "_compressed": True, "_fused": True,
          "_dp_only": True, "_two_phase": True}),
    ],
    "B-olmoe-train": [
        ("baseline", {}),
        ("H1: chunked-vocab loss (same reasoning as cell A)",
         {"loss_chunk": 512}),
        ("H2: bf16 params halve both param reads AND the gradient "
         "all-reduce payload → memory and collective terms drop",
         {"loss_chunk": 512, "param_dtype": "bfloat16"}),
        ("H3: MoE capacity factor 1.25→1.0 cuts dispatch/expert compute "
         "~20% at equal quality envelope",
         {"loss_chunk": 512, "param_dtype": "bfloat16",
          "capacity_factor": 1.0}),
    ],
    "C-mamba2-train": [
        ("baseline", {}),
        ("H1: SSD einsum operands in bf16 (f32 accumulation) halves the "
         "dominant intra-chunk G-matrix traffic",
         {"ssd_f32": False}),
        ("H2: smaller SSD chunk (64→32) quarters the Q² intra-chunk work "
         "per chunk while doubling chunk count → net ~2x less quadratic "
         "compute/bytes",
         {"ssd_f32": False, "ssm_chunk": 32}),
        ("H3: chunked-vocab loss (50k vocab × 1M tokens logits)",
         {"ssd_f32": False, "ssm_chunk": 32, "loss_chunk": 512}),
    ],
}


def lower_compressed_cell(arch: str, shape_name: str, cfg,
                          fused: bool = True, dp_only: bool = False,
                          two_phase: bool = False) -> dict:
    """Lower the majority-vote compressed-DP train step on the production
    mesh and extract the same statistics as `lower_cell` (abstractly — no
    parameter allocation on the 512 host devices)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    from ..configs.base import SHAPES
    from ..distributed.sharding import batch_shardings
    from ..train.optimizer import AdamWConfig
    from ..train.train_step import TrainState, make_compressed_train_step
    from .dryrun import (abstract_state, collective_bytes, input_specs,
                         roofline_terms, scan_corrected, state_shardings)

    shape = SHAPES[shape_name]
    mesh = (make_mesh((256, 1), ("data", "model")) if dp_only
            else make_production_mesh(multi_pod=False))
    state_sds, defs = abstract_state(cfg)
    # compressed DP needs the error-feedback buffer
    state_sds = TrainState(params=state_sds.params, opt=state_sds.opt,
                           error_fb=jax.tree.map(
                               lambda s: jax.ShapeDtypeStruct(
                                   s.shape, jnp.float32), state_sds.params))
    specs = input_specs(cfg, shape)
    step_inner, data_axes = make_compressed_train_step(
        cfg, AdamWConfig(), mesh, fused=fused, two_phase=two_phase)
    bspec = PS(data_axes if len(data_axes) > 1 else data_axes[0])
    # manual over data axes; 'model' stays auto (TP preserved)
    stepped = jax.shard_map(
        step_inner, mesh=mesh, axis_names=set(data_axes),
        in_specs=(jax.tree.map(lambda _: PS(), state_sds),
                  jax.tree.map(lambda _: bspec, specs)),
        out_specs=(jax.tree.map(lambda _: PS(), state_sds),
                   {"loss": PS(), "aux": PS(), "grad_norm": PS(),
                    "lr": PS()}),
        check_vma=False)
    st_shard = state_shardings(defs, mesh)
    st_shard = TrainState(params=st_shard.params, opt=st_shard.opt,
                          error_fb=st_shard.params)
    lowered = jax.jit(stepped, donate_argnums=(0,),
                      in_shardings=(st_shard, batch_shardings(mesh, specs))
                      ).lower(state_sds, specs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    stats = {"arch": arch, "shape": shape_name,
             "mesh": "256x1-dp" if dp_only else "16x16",
             "n_devices": mesh.devices.size, "skipped": False,
             "flops_per_device": ca.get("flops", 0.0),
             "bytes_per_device": ca.get("bytes accessed", 0.0)}
    stats["collectives"] = collective_bytes(compiled.as_text())
    if cfg.scan_layers:
        stats.update(scan_corrected(cfg, shape, arch, shape_name, stats,
                                    mesh.devices.size))
    stats.update(roofline_terms(cfg, shape, stats, mesh.devices.size))
    return stats


def lower_dp_baseline(arch: str, shape_name: str, cfg) -> dict:
    """Plain pjit train step on a pure-DP 256×1 mesh (compression control)."""
    from ..configs.base import SHAPES
    from ..distributed.sharding import batch_shardings
    from ..train.optimizer import AdamWConfig
    from ..train.train_step import make_train_step
    from .dryrun import (abstract_state, collective_bytes, input_specs,
                         roofline_terms, scan_corrected, state_shardings)
    shape = SHAPES[shape_name]
    mesh = make_mesh((256, 1), ("data", "model"))
    state_sds, defs = abstract_state(cfg)
    specs = input_specs(cfg, shape)
    step = make_train_step(cfg, AdamWConfig(), loss_chunk=cfg.loss_chunk)
    lowered = jax.jit(step,
                      in_shardings=(state_shardings(defs, mesh),
                                    batch_shardings(mesh, specs)),
                      donate_argnums=(0,)).lower(state_sds, specs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    stats = {"arch": arch, "shape": shape_name, "mesh": "256x1-dp",
             "n_devices": 256, "skipped": False,
             "flops_per_device": ca.get("flops", 0.0),
             "bytes_per_device": ca.get("bytes accessed", 0.0)}
    stats["collectives"] = collective_bytes(compiled.as_text())
    if cfg.scan_layers:
        stats.update(scan_corrected(cfg, shape, arch, shape_name, stats, 256))
    stats.update(roofline_terms(cfg, shape, stats, 256))
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/hillclimb.json")
    ap.add_argument("--cell", default=None)
    args = ap.parse_args(argv)
    results = []
    for cell, (arch, shape) in CELLS.items():
        if args.cell and args.cell != cell:
            continue
        base_cfg = get_config(arch)
        for hyp, overrides in VARIANTS[cell]:
            ov = dict(overrides)
            compressed = ov.pop("_compressed", False)
            fused = ov.pop("_fused", True)
            dp_only = ov.pop("_dp_only", False)
            two_phase = ov.pop("_two_phase", False)
            cfg = dataclasses.replace(base_cfg, **ov)
            try:
                if compressed:
                    r = lower_compressed_cell(arch, shape, cfg, fused=fused,
                                              dp_only=dp_only,
                                              two_phase=two_phase)
                elif dp_only:
                    r = lower_dp_baseline(arch, shape, cfg)
                else:
                    r = lower_cell(arch, shape, multi_pod=False,
                                   cfg_override=cfg)
            except Exception as e:  # noqa: BLE001
                r = {"error": f"{type(e).__name__}: {e}"}
            r.update({"cell": cell, "hypothesis": hyp,
                      "overrides": overrides})
            results.append(r)
            print(json.dumps({k: v for k, v in r.items()
                              if k not in ("collectives", "memory")}),
                  flush=True)
            jax.clear_caches()
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
