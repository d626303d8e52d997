"""range: ``count`` pairs of bounds ``lo <= v <= hi`` whose width is
``selectivity`` of the code space ``[0, 2**bits)``, drawn from the seed;
each entry is the bound broadcast over ``banks x lanes``, bound under the
two ``names``."""
import jax.numpy as jnp
import numpy as np

from generator import Bound, host_rng


def build(name: str, spec: dict, banks: int, lanes: int, seed: int) -> dict:
    span = 1 << spec["bits"]
    width = max(1, round(spec["selectivity"] * span))
    lo = host_rng(seed, name).integers(0, span - width + 1, spec["count"])
    bounds = {spec["names"][0]: [int(x) for x in lo],
              spec["names"][1]: [int(x) + width - 1 for x in lo]}
    out = {}
    for bname, consts in bounds.items():
        def codes(i, consts=consts):
            return jnp.full((banks, lanes), consts[i], jnp.int32)

        def host(i, consts=consts):
            return np.full((banks, lanes), consts[i], np.int64)
        out[bname] = Bound(len(consts), codes, host)
    return out
