"""Pallas TPU kernels for the paper's compute hot-spots.

:func:`interpret_mode` is the one place that decides how a kernel runs:
compiled on a TPU, in the Pallas interpreter on the CPU.  Any other
platform is an error rather than a silent interpreter run.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (compiled)."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on 'tpu' (compiled) or 'cpu' "
                       f"(interpreted), not on {platform!r}")
