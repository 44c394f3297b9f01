"""Shared Pallas kernel utilities."""

from __future__ import annotations

import functools

import jax

from raft_tpu.utils.pow2 import round_up_safe as round_up  # canonical helper


@functools.lru_cache(maxsize=1)
def interpret_mode() -> bool:
    """True when Pallas TPU kernels must run interpreted (non-TPU backend,
    e.g. the virtual CPU test platform)."""
    return jax.default_backend() != "tpu"
