"""Defaults shared by every Pallas kernel's ops wrapper."""

from __future__ import annotations


def default_interpret() -> bool:
    """Pallas kernels run compiled on TPU and in interpret mode everywhere
    else (CPU CI, tests) — the shared ``interpret=None`` resolution for
    every kernel's ops wrapper."""
    import jax

    return jax.default_backend() != "tpu"
