"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell and extract memory / FLOP / collective statistics for the roofline.

MUST be imported/run before any other jax usage in the process: the first
two lines force 512 host platform devices so ``jax.make_mesh`` can build the
production meshes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b \
        --shape train_4k [--multi-pod] [--all] [--out results.json]
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from ..configs import ARCHS, get_config
from ..configs.base import SHAPES, ModelConfig, ShapeConfig, shape_applicable
from ..distributed.sharding import (batch_shardings, cache_shardings,
                                    replicated, tree_shardings)
from ..models.params import abstract_params
from ..models.transformer import init_cache_shapes, model_defs
from ..serve.decode import make_serve_step
from ..train.data import batch_spec
from ..train.optimizer import AdamWConfig
from ..train.train_step import TrainState, make_train_step
from .mesh import make_mesh, make_production_mesh

# v5e hardware constants (roofline)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link (≈3 links usable per axis hop)

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


# ---------------------------------------------------------------------------
# input_specs
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return batch_spec(cfg, shape)
    if shape.kind == "prefill":
        spec = batch_spec(cfg, shape)
        spec.pop("labels")
        return spec
    # decode: one new token against a seq_len cache
    spec = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    if cfg.rope == "mrope":
        spec["mrope_positions"] = jax.ShapeDtypeStruct((b, 1, 3), jnp.int32)
    if cfg.enc_dec:
        spec["encoder_frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return spec


def abstract_state(cfg: ModelConfig):
    """TrainState ShapeDtypeStructs without allocating anything."""
    defs = model_defs(cfg)
    pdt = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32
    p = abstract_params(defs, dtype=pdt)
    # optimizer moments stay f32 regardless of the parameter dtype
    f32_like = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), t)
    from ..train.optimizer import AdamWState
    opt = AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32),
                     m=f32_like(p), v=f32_like(p))
    return TrainState(params=p, opt=opt, error_fb=None), defs


def state_shardings(defs, mesh):
    ps = tree_shardings(defs, mesh)
    from ..train.optimizer import AdamWState
    return TrainState(params=ps,
                      opt=AdamWState(step=replicated(mesh), m=ps, v=ps),
                      error_fb=None)


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives: sum of result-shape bytes of
    every collective instruction in the optimized module."""
    out = {op: 0 for op in COLLECTIVE_OPS}
    n = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT )?[%\w.-]+ = (.*?) (\S+)\(", stripped)
        if not m:
            continue
        result_shape, opname = m.groups()
        for op in COLLECTIVE_OPS:
            if opname.startswith(op):
                out[op] += _shape_bytes(result_shape)
                n[op] += 1
    return {"bytes": out, "counts": n,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# Scan-trip-count correction probe
# ---------------------------------------------------------------------------
# XLA's cost analysis counts a while-loop (lax.scan) body ONCE regardless of
# trip count, so scanned-layer models under-report FLOPs/bytes by ~n_layers×.
# The probe lowers the same step with python-unrolled 1- and 2-deep stacks at
# a small batch on a 1-device mesh; the difference is the exact per-layer
# cost, scaled linearly by token count (valid because every per-layer term is
# linear in batch at fixed sequence length).

import dataclasses as _dc
import functools as _ft


@_ft.lru_cache(maxsize=None)
def _probe_layer_cost(cfg: ModelConfig,
                      shape_name: str) -> tuple[float, float, int]:
    """(flops, bytes) added per extra layer (global, probe-batch), and the
    probe batch size."""
    shape = SHAPES[shape_name]
    probe_batch = 2 if shape.kind != "decode" else 2
    pshape = _dc.replace(shape, global_batch=probe_batch)
    base = 6 if cfg.family == "hybrid" else 1   # keep the shared-attn cadence
    costs = {}
    for mult in (1, 2):
        n = base * mult
        kw = dict(n_layers=n, scan_layers=False)
        if cfg.layer_pattern:
            kw["layer_pattern"] = ("ssm",) * n
        if cfg.enc_dec:
            kw["n_encoder_layers"] = n
        pcfg = _dc.replace(cfg, **kw)
        mesh = make_mesh((1, 1), ("data", "model"))
        stats = _lower_with(pcfg, cfg.name, pshape, mesh, shape_name)
        costs[mult] = (stats["flops_per_device"], stats["bytes_per_device"])
    d_flops = (costs[2][0] - costs[1][0]) / base
    d_bytes = (costs[2][1] - costs[1][1]) / base
    return d_flops, d_bytes, probe_batch


def scan_corrected(cfg: ModelConfig, shape: ShapeConfig, arch: str,
                   shape_name: str, stats: dict, n_dev: int) -> dict:
    try:
        d_flops, d_bytes, probe_batch = _probe_layer_cost(cfg, shape_name)
    except Exception as e:  # noqa: BLE001 — correction is best-effort
        return {"scan_correction_error": f"{type(e).__name__}: {e}"}
    scale = shape.global_batch / probe_batch
    extra_layers = cfg.n_layers - 1
    if cfg.enc_dec:
        extra_layers += cfg.n_encoder_layers - 1
    add_flops = extra_layers * d_flops * scale / n_dev
    add_bytes = extra_layers * d_bytes * scale / n_dev
    return {
        "flops_per_device_corrected": stats["flops_per_device"] + add_flops,
        "bytes_per_device_corrected": stats["bytes_per_device"] + add_bytes,
        "probe_layer_flops": d_flops * scale,
    }


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _lower_with(cfg, arch: str, shape, mesh, shape_name: str) -> dict:
    """Shared lowering used by both real cells and probes."""
    specs = input_specs(cfg, shape)
    if shape.kind == "train":
        state_sds, defs = abstract_state(cfg)
        st_shard = state_shardings(defs, mesh)
        b_shard = batch_shardings(mesh, specs)
        step = make_train_step(cfg, AdamWConfig(),
                               loss_chunk=cfg.loss_chunk)
        lowered = jax.jit(step, in_shardings=(st_shard, b_shard),
                          donate_argnums=(0,)).lower(state_sds, specs)
    elif shape.kind == "prefill":
        defs = model_defs(cfg)
        p_sds = abstract_params(defs)
        from ..models.transformer import forward

        def prefill_step(params, batch):
            return forward(params, cfg, batch)[0]

        lowered = jax.jit(prefill_step,
                          in_shardings=(tree_shardings(defs, mesh),
                                        batch_shardings(mesh, specs))
                          ).lower(p_sds, specs)
    else:
        defs = model_defs(cfg)
        p_sds = abstract_params(defs)
        cache_sds = init_cache_shapes(cfg, shape.global_batch, shape.seq_len)
        step = make_serve_step(cfg)
        lowered = jax.jit(step,
                          in_shardings=(tree_shardings(defs, mesh),
                                        cache_shardings(mesh, cache_sds),
                                        batch_shardings(mesh, specs)),
                          donate_argnums=(1,)
                          ).lower(p_sds, cache_sds, specs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    return {"compiled": compiled,
            "flops_per_device": ca.get("flops", 0.0),
            "bytes_per_device": ca.get("bytes accessed", 0.0)}


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               compile_: bool = True, cfg_override=None):
    """Lower (and compile) one (arch × shape × mesh) cell; returns stats."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape_name):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "long-context needs sub-quadratic mixer "
                          "(full-attention arch; see DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    stats = {"arch": arch, "shape": shape_name,
             "mesh": "2x16x16" if multi_pod else "16x16",
             "n_devices": mesh.devices.size, "skipped": False}
    if not compile_:
        input_specs(cfg, shape)
        stats["lower_s"] = round(time.time() - t0, 1)
        return stats
    low = _lower_with(cfg, arch, shape, mesh, shape_name)
    compiled = low.pop("compiled")
    stats.update(low)
    stats["compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    if mem is not None:
        stats["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
    stats["collectives"] = collective_bytes(compiled.as_text())
    if cfg.scan_layers:
        stats.update(scan_corrected(cfg, shape, arch, shape_name, stats,
                                    mesh.devices.size))
    stats.update(roofline_terms(cfg, shape, stats, mesh.devices.size))
    return stats


def roofline_terms(cfg: ModelConfig, shape: ShapeConfig, stats: dict,
                   n_dev: int) -> dict:
    flops = (stats.get("flops_per_device_corrected")
             or stats.get("flops_per_device") or 0.0)
    byts = (stats.get("bytes_per_device_corrected")
            or stats.get("bytes_per_device") or 0.0)
    coll = stats.get("collectives", {}).get("total_bytes", 0)
    t_compute = flops / PEAK_FLOPS
    t_memory = byts / HBM_BW
    t_coll = coll / ICI_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    tokens = shape.global_batch * shape.seq_len if shape.kind != "decode" \
        else shape.global_batch
    n_active = cfg.active_param_count()
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens
    hlo_total = flops * n_dev
    return {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / hlo_total) if hlo_total else None,
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def iter_cells():
    for arch in ARCHS:
        for shape in SHAPES:
            yield arch, shape


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args(argv)

    cells = (list(iter_cells()) if args.all
             else [(args.arch, args.shape)])
    meshes = [False, True] if (args.both_meshes or args.multi_pod is False
                               and args.all) else [args.multi_pod]
    results = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                r = lower_cell(arch, shape, multi_pod=mp,
                               compile_=not args.no_compile)
            except Exception as e:  # noqa: BLE001 — cell result records error
                r = {"arch": arch, "shape": shape,
                     "mesh": "2x16x16" if mp else "16x16",
                     "error": f"{type(e).__name__}: {e}"}
            results.append(r)
            print(json.dumps(r), flush=True)
            if not r.get("skipped") and "error" not in r:
                print(f"  [{r['arch']} × {r['shape']} × {r['mesh']}] "
                      f"compile={r.get('compile_s')}s "
                      f"dominant={r.get('dominant')}", file=sys.stderr)
            jax.clear_caches()
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if "error" in r]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
