"""Dispatch wrappers: Pallas kernel on TPU, jnp path elsewhere.

The model code calls these; they keep the program structure identical
between the CPU dry-run and a real TPU run (same shapes, same FLOPs —
only the inner implementation differs).
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp

# Force the jnp path even on TPU (for A/B tests): REPRO_DISABLE_PALLAS=1
_DISABLE = os.environ.get("REPRO_DISABLE_PALLAS", "0") == "1"


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    return _on_tpu() and not _DISABLE


def attention(q, k, v, q_pos, kv_pos, spec, *, block: int = 1024,
              fallback: Optional[Callable] = None):
    """Flash attention: Pallas kernel on TPU; blockwise-jnp elsewhere."""
    if use_pallas():
        from repro.kernels import flash_attention
        return flash_attention.flash_attention(q, k, v, q_pos, kv_pos, spec,
                                               block_kv=block)
    assert fallback is not None
    return fallback(q, k, v, q_pos, kv_pos, spec, block)


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = 256,
                fallback: Optional[Callable] = None):
    """Mamba2 SSD chunked scan: Pallas on TPU; jnp reference elsewhere."""
    if use_pallas():
        from repro.kernels import ssd_scan
        return ssd_scan.ssd_scan(x, dt, A, B, C, D, chunk=chunk)
    assert fallback is not None
    return fallback(x, dt, A, B, C, D, chunk)
