"""count: a unit returns the set lanes of its last output, a 1-bit
plane-resident answer, counted on the device in one launch and read by
the host.  Compared: ``wrong_lanes`` of that output and ``wrong_counts``,
the units whose count differs from the reference's."""
import jax
import jax.numpy as jnp

from generator import LANE_WORD, last, wrong_lanes


@jax.jit
def _count(planes):
    return jnp.sum(jax.lax.population_count(planes[..., 0, :]))


def read(spec, steps, outs) -> int:
    return int(_count(outs[last(steps)].planes))


def keep(spec, steps, outs, got):
    return outs[last(steps)], got


def compare(spec, steps, kept, env) -> dict:
    out, count = kept
    want = env[last(steps)]
    return {"wrong_lanes": wrong_lanes(out, want),
            "wrong_counts": int(count != int(want.sum()))}


def out_bytes(spec, steps, banks: int, lanes: int) -> int:
    out_bits = steps[-1].get("out_bits") or steps[-1]["n_bits"]
    return banks * out_bits * (lanes // LANE_WORD) * 4
