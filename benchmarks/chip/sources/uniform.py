"""uniform: ``count`` entries of ``banks x lanes`` codes, uniform on
``[0, 2**bits)``, drawn on the device from the seed, bound under the
source's own name."""
import jax
import jax.numpy as jnp
import numpy as np

from generator import Bound, device_key


def build(name: str, spec: dict, banks: int, lanes: int, seed: int) -> dict:
    shape = (spec["count"], banks, lanes)
    draw = jax.jit(lambda k, shape=shape, hi=1 << spec["bits"]:
                   jax.random.randint(k, shape, 0, hi, jnp.int32))
    codes = draw(device_key(seed, name))

    def host(i):
        return np.asarray(codes[i]).astype(np.int64)
    return {name: Bound(spec["count"], lambda i: codes[i], host)}
