"""Pallas int8 quantize/dequantize — the wire codec as TPU kernels.

``repro.dist.compression`` owns the symmetric max-abs int8 wire format
(one fp32 scale per tensor, round-to-nearest, ``|x − q·s| ≤ s/2``).
These kernels implement the same codec in Pallas so that on TPU the
quantize/dequantize around the gradient collective runs as fused VMEM
kernels instead of XLA elementwise ops (ROADMAP item). Numerics are
bit-identical to the jnp reference — asserted in tests/test_kernels.py
via interpret mode, which is also what keeps this file testable on the
CPU container.

Layout: the tensor is flattened and tiled to ``(rows, 128)`` lanes with
zero padding (zeros never change a max-abs and quantize to 0, so the
padding is dropped after the call). Three kernels:

  * ``_absmax_kernel``   — per-block max|x| folded to one (8, 128)
    vreg-shaped tile per grid step; the wrapper takes the max over the
    tiles (Mosaic cannot store a scalar to VMEM, and a max is exact in
    any order, so this stays bit-identical);
  * ``_quantize_kernel`` — elementwise scale-divide/round/clip to int8
    on ``(block_rows, 128)`` tiles (block_rows is a multiple of 32, the
    int8 sublane tile);
  * ``_dequantize_kernel`` — elementwise int8·scale back to fp32.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
_SCALE_SPEC = pl.BlockSpec((1, 1), lambda i: (0, 0))


def _absmax_kernel(x_ref, out_ref):
    a = jnp.abs(x_ref[...])                           # [block_rows, 128]
    out_ref[...] = jnp.max(a.reshape(-1, SUBLANES, LANES), axis=0)


def _quantize_kernel(x_ref, scale_ref, q_ref):
    # divide, don't multiply by a reciprocal: round(x/s) and
    # round(x·(1/s)) differ at half-ulp boundaries, and the contract is
    # bit-identity with the jnp reference codec
    s = scale_ref[0, 0]
    safe = jnp.where(s > 0, s, 1.0)
    q = jnp.round(x_ref[...] / safe)
    q_ref[...] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def _dequantize_kernel(q_ref, scale_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[0, 0]


def _tile(x: jax.Array, block_rows: int, dtype=None
          ) -> Tuple[jax.Array, int]:
    """Flatten + zero-pad to a (rows, LANES) tile grid; rows a multiple
    of ``block_rows`` (itself a multiple of the int8 sublane tile 32)."""
    flat = x.reshape(-1)
    if dtype is not None:
        flat = flat.astype(dtype)
    per_block = block_rows * LANES
    n_blocks = max(-(-flat.size // per_block), 1)
    padded = n_blocks * per_block
    flat = jnp.pad(flat, (0, padded - flat.size))
    return flat.reshape(-1, LANES), n_blocks


def quantize_int8_pallas(x: jax.Array, *, block_rows: int = 64,
                         interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric max-abs int8 quantization -> (int8 values, fp32 scale).

    Same contract as ``repro.dist.compression.quantize_int8``; shape and
    round-to-nearest numerics match the jnp reference exactly.

    Deliberately *not* jit-wrapped: XLA rewrites the divide-by-127
    constant into a reciprocal multiply inside a jit scope, which would
    put a jitted wrapper one scale-ulp away from the eager jnp codec.
    Left un-wrapped, both implementations see the same context — eager
    vs eager and traced vs traced — and stay bit-identical (the
    dispatcher in ``repro.dist.compression`` is always called from
    inside the caller's jit anyway).
    """
    assert block_rows % 32 == 0, "int8 tiles are (32, 128)"
    tiles, n_blocks = _tile(x, block_rows, dtype=jnp.float32)
    grid = (n_blocks,)
    block = (block_rows, LANES)
    partial_max = pl.pallas_call(
        _absmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * SUBLANES, LANES),
                                       jnp.float32),
        interpret=interpret,
        name="int8_absmax",
    )(tiles)
    scale = jnp.max(partial_max).reshape(1, 1) / 127.0
    q = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i: (i, 0)), _SCALE_SPEC],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.int8),
        interpret=interpret,
        name="int8_quantize",
    )(tiles, scale)
    return q.reshape(-1)[:x.size].reshape(x.shape), scale.reshape(())


def dequantize_int8_pallas(q: jax.Array, scale: jax.Array, *,
                           block_rows: int = 64,
                           interpret: bool = False) -> jax.Array:
    """int8 values × fp32 scale -> fp32, tiled like the quantizer (and
    un-jitted for the same bit-identity reason)."""
    assert block_rows % 32 == 0, "int8 tiles are (32, 128)"
    tiles, n_blocks = _tile(q, block_rows)
    block = (block_rows, LANES)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(block, lambda i: (i, 0)), _SCALE_SPEC],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
        interpret=interpret,
        name="int8_dequantize",
    )(tiles, jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return out.reshape(-1)[:q.size].reshape(q.shape)
