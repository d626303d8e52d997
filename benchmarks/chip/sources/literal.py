"""literal: one named constant, ``value``, broadcast over ``banks x
lanes`` (a query's parameter or a code to compare with), one entry, bound
under the spec's own name at the spec's ``bits``."""
import jax.numpy as jnp
import numpy as np

from generator import Bound


def build(name: str, spec: dict, banks: int, lanes: int, seed: int) -> dict:
    value = int(spec["value"])
    if not 0 <= value < 1 << spec["bits"]:
        raise ValueError(f"literal {name}: {value} does not fit "
                         f"{spec['bits']} bits")
    return {name: Bound(1, lambda i: jnp.full((banks, lanes), value,
                                              jnp.int32),
                        lambda i: np.full((banks, lanes), value, np.int64))}
