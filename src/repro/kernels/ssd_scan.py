"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the sequence is
split into chunks of Q tokens; within a chunk the computation is two
MXU-shaped matmuls (C·Bᵀ "attention" score and score·X), and across chunks
an O(1)-state recurrence is carried in fp32 VMEM scratch — the chunk axis
is the innermost (sequential) grid dimension, exactly like the KV axis of
flash attention.

  grid = (batch, heads, n_chunks)
  blocks: x (Q, P) · dt as a (Q, 1) column and a (1, Q) row · B/C (Q, N)
  in VMEM; A and D whole in SMEM, read per head
  scratch: state (P, N) fp32, persists across the chunk dimension

Mosaic needs the last two dims of every block to be (8, 128)-aligned
or whole, so the wrapper moves heads ahead of the sequence
([b,h,l,p], [b,g,l,n]) and the kernel sees sequence-major tiles. The
in-chunk cumulative sum is a matmul with a lower-triangular ones
matrix, in both orientations, so no vector is ever transposed.

Outputs y (Q, P) per block plus the final state (for decode prefill).
Every decay is the exp of a sum of dt·A (``exp(total - csum)`` for the
state update), never a quotient of exponentials.

The backward pass is the VJP of ``models.ssm.ssd_reference``, the jnp
chunked scan, recomputed from the saved inputs (the kernel is
forward-only; pallas_call has no transpose rule). In interpret mode the
forward and this backward are checked against ``ssd_reference`` and
against the plain quadratic SSD in float64, in the values and in the
gradients of every input, with chunk sums of dt·A down to -1.4e4
(``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

from repro.models.ssm import ssd_reference

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, dtc_ref, dtr_ref, A_ref, B_ref, C_ref, D_ref,  # in
            y_ref, st_ref,                                        # out
            state_ref,                                            # scratch
            *, n_chunks: int):
    h = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)               # [Q, P]
    dt_col = dtc_ref[0, 0]                            # [Q, 1]
    dt_row = dtr_ref[0, 0]                            # [1, Q]
    Bm = B_ref[0, 0].astype(jnp.float32)              # [Q, N]
    Cm = C_ref[0, 0].astype(jnp.float32)              # [Q, N]
    A = A_ref[h]
    D = D_ref[h]

    Q = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = col <= row
    tri = causal.astype(jnp.float32)                  # tri[q, k] = k <= q
    # inclusive cumsum of dt·A as a column and as a row
    csum_col = _dot(tri, dt_col * A, ((1,), (0,)))    # [Q, 1]
    csum_row = _dot(dt_row * A, tri, ((1,), (1,)))    # [1, Q]
    total = jnp.sum(dt_row * A, axis=1, keepdims=True)  # [1, 1]
    # intra-chunk decay L[q,k] = exp(csum[q]-csum[k]) for k<=q
    L = jnp.where(causal, jnp.exp(csum_col - csum_row), 0.0)

    scores = _dot(Cm, Bm, ((1,), (1,))) * L
    y = _dot(scores * dt_row, x, ((1,), (0,)))                 # intra

    # inter-chunk: y += (C * exp(csum)) @ state_prev
    y = y + _dot(Cm * jnp.exp(csum_col), state_ref[...], ((1,), (1,)))
    y = y + x * D
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: state_new = state*chunk_decay + X^T(dt·decay_states·B)
    decay_states = jnp.exp(total - csum_col)                   # [Q, 1]
    upd = _dot(x, Bm * (dt_col * decay_states), ((0,), (0,)))  # [P, N]
    state_ref[...] = state_ref[...] * jnp.exp(total) + upd

    @pl.when(c == n_chunks - 1)
    def _emit():
        st_ref[0, 0] = state_ref[...].astype(st_ref.dtype)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 256,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: [b,l,h,p]; dt: [b,l,h]; A,D: [h]; B,C: [b,l,g,n].
    Returns (y [b,l,h,p], final_state [b,h,p,n]). l % chunk == 0."""
    return _ssd(x, dt, A, B, C, D, chunk, interpret)


def _ssd_forward(x, dt, A, B, C, D, chunk, interpret):
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0, (l, chunk)
    nch = l // chunk
    rep = h // g

    kernel = functools.partial(_kernel, n_chunks=nch)
    dt = dt.astype(jnp.float32).transpose(0, 2, 1)           # [b,h,l]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def head_block(bi, hi, ci):
        return (bi, hi, ci, 0)

    def group_block(bi, hi, ci, rep=rep):
        return (bi, hi // rep, ci, 0)

    y, st = pl.pallas_call(
        kernel,
        grid=(b, h, nch),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), head_block),
            pl.BlockSpec((1, 1, chunk, 1), head_block),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            smem,
            pl.BlockSpec((1, 1, chunk, n), group_block),
            pl.BlockSpec((1, 1, chunk, n), group_block),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), head_block),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan_fwd",
    )(x.transpose(0, 2, 1, 3), dt[..., None], dt[:, :, None, :],
      A.astype(jnp.float32), B.transpose(0, 2, 1, 3),
      C.transpose(0, 2, 1, 3), D.astype(jnp.float32))
    return y.transpose(0, 2, 1, 3), st


def _ssd_fwd(x, dt, A, B, C, D, chunk, interpret):
    return (_ssd_forward(x, dt, A, B, C, D, chunk, interpret),
            (x, dt, A, B, C, D))


def _ssd_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(
        lambda *a: ssd_reference(*a, chunk=chunk, return_state=True), *res)
    return vjp(g)


_ssd = jax.custom_vjp(_ssd_forward, nondiff_argnums=(6, 7))
_ssd.defvjp(_ssd_fwd, _ssd_bwd)
