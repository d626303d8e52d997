"""The kernels and the compiled executor of the main path compile for a TPU
v5e at real widths.

Nothing here runs on a chip: each test compiles for a described (not
attached) ``v5e:2x2`` topology and checks that the program holds a compiled
TPU kernel (``tpu_custom_call``), or for the executor that its command loop
copies no row file, so a kernel the TPU compiler refuses fails here
instead of on the chip.  The topology is described inside a
fixture, never at import: only the worker that runs this file loads the
TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.circuits import compile_operation
from repro.core.trace import lower_program
from repro.core.unrolled import simdram_execute
from repro.kernels.bitplane_transpose import bitplane_transpose
from repro.kernels.bitserial_matmul import bitserial_matmul
from repro.kernels.ops import run_trace_kernel
from repro.kernels.uprog_executor import uprog_execute

WORDS = 2048          # 65,536 lanes: one 8 kB row per bank
BANKS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("banks", [None, BANKS], ids=["unbanked", "banked"])
@pytest.mark.parametrize("op,n_bits", [("addition", 8),
                                       ("multiplication", 8),
                                       ("division", 16)])
def test_uprog_execute_compiles(one_chip, op, n_bits, banks):
    """16-bit division is the longest built-in trace (5,825 commands): its
    command stream must still fit SMEM."""
    trace = lower_program(compile_operation(op, n_bits))
    if banks is None:
        fn = jax.jit(lambda c, r: uprog_execute(c, r, interpret=False))
        args = (_sds(trace.cmds.shape, jnp.int32, one_chip),
                _sds((trace.n_rows, WORDS), jnp.uint32, one_chip))
    else:
        fn = jax.jit(jax.vmap(
            lambda ops: run_trace_kernel(trace, ops, interpret=False)))
        args = ({name: _sds((banks, n_bits, WORDS), jnp.uint32, one_chip)
                 for name in dict.fromkeys(trace.inputs)},)
    _assert_kernel(fn.lower(*args).compile())


def test_bitplane_transpose_compiles(one_chip):
    fn = jax.jit(lambda g: bitplane_transpose(g, interpret=False))
    _assert_kernel(fn.lower(_sds((WORDS, 32), jnp.uint32, one_chip))
                   .compile())


def test_bitserial_matmul_compiles_with_default_blocks(one_chip):
    m = n = 1024
    k = 4096
    fn = jax.jit(lambda a, b: bitserial_matmul(a, b, k, interpret=False))
    _assert_kernel(fn.lower(_sds((m, k // 32), jnp.uint32, one_chip),
                            _sds((n, k // 32), jnp.uint32, one_chip))
                   .compile())


@pytest.mark.parametrize("kernel", ["uprog_execute", "bitplane_transpose",
                                    "bitserial_matmul"])
def test_kernel_names_are_stable(one_chip, kernel):
    """Each kernel's TPU custom call carries the kernel's own name, so a
    profile finds it by name."""
    if kernel == "uprog_execute":
        trace = lower_program(compile_operation("addition", 8))
        fn = jax.jit(lambda c, r: uprog_execute(c, r, interpret=False))
        args = (_sds(trace.cmds.shape, jnp.int32, one_chip),
                _sds((trace.n_rows, WORDS), jnp.uint32, one_chip))
    elif kernel == "bitplane_transpose":
        fn = jax.jit(lambda g: bitplane_transpose(g, interpret=False))
        args = (_sds((WORDS, 32), jnp.uint32, one_chip),)
    else:
        fn = jax.jit(lambda a, b: bitserial_matmul(a, b, 4096,
                                                   interpret=False))
        args = (_sds((1024, 128), jnp.uint32, one_chip),) * 2
    assert f'kernel_name = "{kernel}"' in fn.lower(*args).as_text()


@pytest.mark.parametrize("op,n_bits", [("addition", 16),
                                       ("multiplication", 32)])
def test_simdram_execute_keeps_one_row_file(one_chip, op, n_bits):
    """The ``unrolled`` backend's program compiles at real widths and its
    command loop updates the row file in place: the program holds no copy
    of the whole ``uint32[n_rows, banks, W]`` row file."""
    trace = lower_program(compile_operation(op, n_bits))
    planes = tuple(_sds((BANKS, n_bits, WORDS), jnp.uint32, one_chip)
                   for _ in dict.fromkeys(trace.inputs))
    args = (planes, _sds((trace.n_rows,), jnp.int32, one_chip),
            _sds((len(trace.cmds), 6), jnp.int32, one_chip),
            {name: _sds((n_bits,), jnp.int32, one_chip)
             for name in trace.outputs})
    text = simdram_execute.lower(*args).compile().as_text()
    assert " while(" in text
    row_file = f"u32[{trace.n_rows},{BANKS},{WORDS}]"
    copies = [line for line in text.splitlines()
              if f"= {row_file}" in line and " copy(" in line]
    assert copies == []
