"""Pallas TPU kernels for the paper's hot spots, one module each, with
pure-jnp oracles in ``ref.py``.

Every kernel takes ``interpret: bool | None``. ``None`` picks the mode from
the backend: the kernel body is interpreted only where JAX's default
backend is the CPU; on a TPU it is always compiled by Mosaic, never
interpreted and never replaced by the jnp reference.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` if given, else True only on the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
