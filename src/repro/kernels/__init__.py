"""Pallas kernels for the compute sites the offload search can move off XLA.

Every kernel takes ``interpret=None``, which means: compile for the chip
when the default backend is a TPU, run the Pallas interpreter otherwise.
The choice is made when the call is traced, never at import time.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
