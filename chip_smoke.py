"""Run the SIMDRAM main path once on one TPU chip and check every result.

    python chip_smoke.py [--seed N]

Everything runs in this one process; it starts no other.  The phases:

a. the machine path at paper scale: ``SimdramMachine(banks=16)`` on the
   ``pallas`` and ``unrolled`` backends runs all 16 built-in ops at 8 bits,
   and addition, multiplication and division at 16 bits, on operands of
   16 banks x 65,536 lanes (one 8 kB row per bank);
b. one fused plane-resident chain (mul -> add -> relu, 8 bits, 16 banks)
   and one ``submit``/``drain(batch=True)`` round on the same machine;
c. the served paths: a ``SimdramServer`` of 2 machines x 8 banks answering
   8 sessions of 8 tokens, and ``greedy_decode`` of the full-width
   qwen1.5-0.5b with the SIMDRAM sampler on a ``pallas`` machine;
d. the kernels alone: ``bitplane_transpose`` on 65,536 elements and
   ``bitserial_matmul`` at 1024 x 1024, K = 4096.

Every phase is compared with a plain numpy reference written here, and any
mismatch fails the run.  Inputs and weights come from ``--seed``.  The
progress lines (phase, shapes, wall seconds, pass or fail) are not
measurements: they include compilation.  The script exits non-zero, and
prints no result, when JAX finds no TPU or any phase fails; otherwise its
last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.backends import get_backend  # noqa: E402
from repro.core.circuits import ALL_OPS  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.kernels.bitserial_matmul import bitserial_matmul  # noqa: E402
from repro.kernels.ops import transpose_to_planes  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.models.transformer import init_cache_shapes, model_defs  # noqa: E402
from repro.ops.bbops import (bbop_add, bbop_mul, bbop_relu,  # noqa: E402
                             simdram_pipeline)
from repro.serve.decode import (greedy_decode, make_prefill,  # noqa: E402
                                make_serve_step)
from repro.serve.server import SimdramServer  # noqa: E402
from repro.simdram.layout import to_bitplanes  # noqa: E402
from repro.simdram.machine import SimdramMachine  # noqa: E402

BANKS = 16                 # the paper's 16-bank scaling
LANES = 65_536             # one 8 kB DRAM row per bank (simdram/timing.py)
SERVE_CONFIGS = ("qwen1_5_0_5b", "mamba2_130m")
DECODE_BATCH, DECODE_PROMPT, DECODE_TOKENS = 4, 16, 8
SAMPLER_BITS = 8


def decode_config():
    """The served model: qwen1.5-0.5b at its published widths."""
    return get_config("qwen1_5_0_5b")


# ---------------------------------------------------------------------------
# numpy references
# ---------------------------------------------------------------------------

def ref_op(op: str, ins: dict, n: int) -> np.ndarray:
    """Plain numpy semantics of every built-in op on unsigned n-bit ints."""
    mask = (1 << n) - 1
    a, b = ins.get("a"), ins.get("b")
    if op == "addition":
        return (a + b) & mask
    if op == "subtraction":
        return (a - b) & mask
    if op == "multiplication":
        return (a * b) & mask
    if op == "division":
        return a // b
    if op == "greater":
        return (a > b).astype(np.int64)
    if op == "greater_equal":
        return (a >= b).astype(np.int64)
    if op == "equal":
        return (a == b).astype(np.int64)
    if op == "if_else":
        return np.where(ins["sel"] == 1, a, b)
    if op == "bitcount":
        return np.bitwise_count(a).astype(np.int64)
    if op in ("and_reduction", "or_reduction", "xor_reduction"):
        s0, s1, s2 = ins["s0"], ins["s1"], ins["s2"]
        return {"and_reduction": s0 & s1 & s2, "or_reduction": s0 | s1 | s2,
                "xor_reduction": s0 ^ s1 ^ s2}[op]
    signed = np.where(a >= 1 << (n - 1), a - (1 << n), a)
    if op == "relu":
        return np.where(signed >= 0, a, 0)
    if op == "abs":
        return np.abs(signed) & mask
    if op == "maximum":
        return np.maximum(a, b)
    if op == "minimum":
        return np.minimum(a, b)
    raise KeyError(op)


def out_bits(op: str, n: int) -> int:
    if op in ("greater", "greater_equal", "equal"):
        return 1
    if op == "bitcount":
        return n.bit_length()
    return n


def op_inputs(rng, op: str, n: int, shape) -> dict:
    """Seeded operands for one op (division gets a non-zero divisor, equal
    an operand that matches on about half the lanes)."""
    hi = 1 << n
    a = rng.integers(0, hi, shape)
    b = rng.integers(0, hi, shape)
    if op == "division":
        b = rng.integers(1, hi, shape)
    if op == "equal":
        b = np.where(rng.random(shape) < 0.5, a, b)
    ins = {"a": a, "b": b, "sel": rng.integers(0, 2, shape),
           "s0": a, "s1": b, "s2": rng.integers(0, hi, shape)}
    return ins


def np_bitplanes(x: np.ndarray, n_bits: int) -> np.ndarray:
    """uint32[E] -> uint32[n_bits, E/32]: plane i, word j, bit k holds bit i
    of element 32 j + k."""
    shifts = np.arange(n_bits, dtype=np.uint64)[:, None]
    bits = (x[None, :].astype(np.uint64) >> shifts) & np.uint64(1)
    bits = bits.reshape(n_bits, -1, 32)
    return (bits << np.arange(32, dtype=np.uint64)).sum(-1).astype(np.uint32)


def np_quantize(logits: np.ndarray, n_bits: int) -> np.ndarray:
    """The sampler's per-row affine quantization, in float32 numpy."""
    finite = np.isfinite(logits)
    lo = np.min(np.where(finite, logits, np.inf), -1, keepdims=True)
    hi = np.max(np.where(finite, logits, -np.inf), -1, keepdims=True)
    scale = np.float32(2 ** n_bits - 1) / np.maximum(hi - lo,
                                                     np.float32(1e-9))
    q = np.round((logits - lo) * scale)
    return np.clip(np.where(finite, q, 0), 0, 2 ** n_bits - 1)


def check(label: str, got, want) -> None:
    got = np.asarray(got).astype(np.int64)
    want = np.asarray(want).astype(np.int64)
    if got.shape != want.shape:
        raise ValueError(f"{label}: shape {got.shape} != {want.shape}")
    bad = int((got != want).sum())
    if bad:
        raise ValueError(f"{label}: {bad} of {want.size} values differ")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def check_compiled_dispatch(machine: SimdramMachine) -> None:
    """One banked pallas dispatch must lower to a compiled TPU kernel."""
    if interpret_mode():
        raise RuntimeError("Pallas kernels would run in the interpreter")
    _, trace = machine.op("addition").program(8)
    fn = get_backend("pallas")
    ops = {name: jax.ShapeDtypeStruct((BANKS, 8, LANES // 32), jnp.uint32)
           for name in dict.fromkeys(trace.inputs)}
    text = jax.jit(jax.vmap(lambda o: fn(trace, o))).lower(ops).as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError("banked pallas dispatch has no tpu_custom_call")


def phase_machine_ops(rng, backend: str) -> str:
    m = SimdramMachine(banks=BANKS, backend=backend)
    if backend == "pallas":
        check_compiled_dispatch(m)
    shape = (BANKS, LANES)
    cases = [(op, 8) for op in ALL_OPS] + [
        (op, 16) for op in ("addition", "multiplication", "division")]
    for op, n in cases:
        ins = op_inputs(rng, op, n, shape)
        bound = m.op(op)
        names = tuple(dict.fromkeys(bound.program(n)[0].inputs))
        ob = out_bits(op, n)
        got = bound(*(jnp.asarray(ins[k], jnp.int32) for k in names),
                    n_bits=n, out_bits=ob)
        check(f"{backend} {op}/{n}", got, ref_op(op, ins, n))
    return f"{len(cases)} op widths, {BANKS} banks x {LANES} lanes"


def phase_fused_and_drain(rng) -> str:
    m = SimdramMachine(banks=BANKS, backend="pallas")
    shape = (BANKS, LANES)
    a, b, c = (rng.integers(0, 256, shape) for _ in range(3))
    with simdram_pipeline(banks=BANKS, machine=m, fused_trace=True,
                          timed=True) as p:
        pa, pb, pc = p.load([jnp.asarray(x, jnp.int32) for x in (a, b, c)],
                            8)
        got = p.store(bbop_relu(bbop_add(bbop_mul(pa, pb, 8), pc, 8), 8))
    prod = ref_op("multiplication", {"a": a, "b": b}, 8)
    want = ref_op("relu", {"a": ref_op("addition", {"a": prod, "b": c}, 8)},
                  8)
    check("fused mul>add>relu", got, want)
    if m.stats.n_programs != 1:
        raise ValueError(f"fused chain ran as {m.stats.n_programs} "
                         "programs, expected 1")
    subs = []
    for k in range(BANKS):
        op = ("addition", "multiplication")[k % 2]
        x, y = rng.integers(0, 256, LANES), rng.integers(0, 256, LANES)
        fut = m.submit(op, jnp.asarray(x, jnp.int32),
                       jnp.asarray(y, jnp.int32), n_bits=8,
                       tenant=f"t{k % 4}")
        subs.append((op, x, y, fut))
    m.drain(batch=True)
    for op, x, y, fut in subs:
        check(f"drained {op}", fut.result(),
              ref_op(op, {"a": x, "b": y}, 8))
    return (f"chain on {BANKS} banks x {LANES} lanes, "
            f"{len(subs)} submissions of {LANES} lanes")


def phase_server(rng, backend: str) -> str:
    server = SimdramServer(n_machines=2, n_banks=8, backend=backend)
    sessions = []
    for u in range(8):
        h = server.submit_session(SERVE_CONFIGS[u % len(SERVE_CONFIGS)],
                                  n_tokens=8, arrival_ns=u * 500.0,
                                  seed=int(rng.integers(2 ** 31)))
        s = h.session
        sessions.append((h, s.profile.op, s.profile.n_bits, s.a.copy(),
                         s.b.copy()))
    server.run()
    for h, op, n, a, b in sessions:
        if not h.done():
            raise ValueError(f"session {h.session.tenant} did not retire")
        for _ in range(h.session.n_tokens):
            a = ref_op(op, {"a": a, "b": b}, n)
        check(f"session {h.session.tenant} {op}", h.result(), a)
    return "2 machines x 8 banks, 8 sessions x 8 tokens"


def phase_decode(seed: int) -> str:
    cfg = decode_config()
    params = init_params(model_defs(cfg), jax.random.key(seed))
    prompt = jax.random.randint(jax.random.key(seed + 1),
                                (DECODE_BATCH, DECODE_PROMPT), 0, cfg.vocab,
                                jnp.int32)
    m = SimdramMachine(backend="pallas")
    t0 = time.perf_counter()
    tokens = np.asarray(greedy_decode(params, cfg, prompt, DECODE_TOKENS,
                                      sampler="simdram", machine=m))
    t_decode = time.perf_counter() - t0
    if tokens.shape != (DECODE_BATCH, DECODE_TOKENS):
        raise ValueError(f"decoded tokens have shape {tokens.shape}")
    # Replay the decode with the picked tokens through the same prefill and
    # step programs: the device is deterministic, so these are the logits
    # the sampler saw.
    caches = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                          init_cache_shapes(cfg, DECODE_BATCH,
                                            DECODE_PROMPT + DECODE_TOKENS))
    logits, caches = jax.jit(make_prefill(cfg))(params, {"tokens": prompt},
                                                caches)
    step = jax.jit(make_serve_step(cfg))
    seen = [logits[:, -1]]
    for t in range(DECODE_TOKENS - 1):
        logits, caches = step(params, caches,
                              {"tokens": jnp.asarray(tokens[:, t:t + 1])})
        seen.append(logits[:, -1])
    rows = np.arange(DECODE_BATCH)
    for t, lg in enumerate(seen):
        q = np_quantize(np.asarray(lg, np.float32), SAMPLER_BITS)
        picked = q[rows, tokens[:, t]]
        if not np.array_equal(picked, q.max(-1)):
            raise ValueError(f"token {t}: sampler picked bins {picked}, "
                             f"maximal bins are {q.max(-1)}")
    return (f"{cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
            f"vocab {cfg.vocab}, batch {DECODE_BATCH} prompt "
            f"{DECODE_PROMPT} +{DECODE_TOKENS} tokens (decode "
            f"{t_decode:.1f} s wall, replay and check "
            f"{time.perf_counter() - t0 - t_decode:.1f} s)")


def phase_kernels(rng) -> str:
    x = rng.integers(0, 2 ** 32, LANES, dtype=np.uint64).astype(np.uint32)
    xj = jnp.asarray(x.view(np.int32))
    planes = transpose_to_planes(xj, 32)
    check("bitplane_transpose vs numpy", planes, np_bitplanes(x, 32))
    check("bitplane_transpose vs to_bitplanes", planes,
          to_bitplanes(xj, 32))
    m, n, k = 1024, 1024, 4096
    a = rng.integers(0, 2 ** 32, (m, k // 32), dtype=np.uint64)
    b = rng.integers(0, 2 ** 32, (n, k // 32), dtype=np.uint64)
    a, b = a.astype(np.uint32), b.astype(np.uint32)
    got = bitserial_matmul(jnp.asarray(a), jnp.asarray(b), k)
    want = np.empty((m, n), np.int64)
    for i in range(0, m, 128):    # XNOR-popcount, a row block at a time
        mism = np.bitwise_count(a[i:i + 128, None, :] ^ b[None, :, :])
        want[i:i + 128] = k - 2 * mism.sum(-1, dtype=np.int64)
    check("bitserial_matmul", got, want)
    return f"transpose {LANES} elements, matmul {m}x{n} K={k}"


def run_phases(seed: int) -> bool:
    """Run every phase, printing one progress line each; True if all
    passed.  A failed phase is reported and the run goes on, so one run
    shows every failure."""
    rng = np.random.default_rng(seed)
    phases = [
        ("a/machine-pallas", lambda: phase_machine_ops(rng, "pallas")),
        ("a/machine-unrolled", lambda: phase_machine_ops(rng, "unrolled")),
        ("b/fused-chain+drain", lambda: phase_fused_and_drain(rng)),
        ("c/server-unrolled", lambda: phase_server(rng, "unrolled")),
        ("c/server-pallas", lambda: phase_server(rng, "pallas")),
        ("c/greedy-decode", lambda: phase_decode(seed)),
        ("d/kernels", lambda: phase_kernels(rng)),
    ]
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            what = fn()
            status = "pass"
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            what, status, ok = f"{type(e).__name__}: {e}", "FAIL", False
        print(f"phase {name}: {what}; {time.perf_counter() - t0:.1f} s wall; "
              f"{status}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    configure_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if not run_phases(args.seed):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
