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

The backward is two more kernels, recomputing from the saved inputs
(x, dt, A, B, C, D) with positions on lanes (x, dy [P, Q], B, C [N, Q]
per block, so per-position vectors are rows):

  ``ssd_scan_bwd_states``  grid (batch, groups, head blocks, n_chunks):
      the chunk-start states, carried in fp32 VMEM and written to HBM
  ``ssd_scan_bwd_grads``   grid (batch, groups, n_chunks reversed,
      head blocks): per chunk and B/C group C·Bᵀ once; per head the
      products with dy, dx, the state's gradient dS (P, N) carried in
      fp32 VMEM across chunks; the heads' score gradients summed in fp32
      VMEM before the group's dB and dC products

A block holds as many of a group's heads as fit a VMEM budget; a loop
runs them. MXU operands are in the inputs' dtype with fp32
accumulation. The in-chunk cumulative sum cs of dt·A and its reverse in
the backward are products with a triangular ones matrix (outside the
kernels), and every decay there too is the exp of a sum of dt·A. The
gradients of dt, A and D leave the kernel as per-position rows.

In interpret mode the forward and the backward are checked against
``models.ssm.ssd_reference`` (the CPU path, and the oracle) and against
the plain quadratic SSD in float64, in the values and in the gradients
of every input, with chunk sums of dt·A down to -1.4e4
(``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp


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


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_BLOCK_BYTES = 4 << 20     # VMEM for one grid step's per-head blocks


def _mm(a, b, dims, dtype):
    """a·b on the MXU with operands in ``dtype`` (the inputs' own) and
    float32 accumulation; float32 operands at full precision."""
    prec = _HIGHEST if dtype == jnp.float32 else None
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (dims, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _heads_per_block(rep, q, p, n, itemsize):
    """Heads of one B/C group a grid step takes: the most that divide the
    group and keep the step's per-head blocks (x, dy, dx, the chunk-start
    state, the final state's cotangent, the per-position rows) within
    ``_BLOCK_BYTES``."""
    per_head = 3 * q * p * itemsize + p * n * (4 + itemsize) + 2 * 8 * q * 4
    fits = [d for d in range(1, rep + 1)
            if rep % d == 0 and d * per_head <= _BLOCK_BYTES]
    return max(fits, default=1)


def _chunk_total(cs):
    """T = cs[Q-1], the chunk's sum of dt·A, as [1, 1]."""
    last = jax.lax.broadcasted_iota(jnp.int32, cs.shape, 1) == \
        cs.shape[1] - 1
    return jnp.sum(jnp.where(last, cs, 0.0), axis=1, keepdims=True)


def _states_kernel(x_ref, r_ref, B_ref,               # in
                   s0_ref,                            # out
                   st_ref,                            # scratch
                   *, heads: int):
    """Chunk-start states, chunks in order: s0[c] = state before chunk c,
    carried in fp32 VMEM as the forward carries it."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    Bt = B_ref[0, 0]                                  # [N, Q]
    dtype = Bt.dtype

    def head(i, carry):
        rows = r_ref[0, i]                            # [2, Q]: dt, cs
        total = _chunk_total(rows[1:2])
        st = st_ref[i]
        s0_ref[0, i, 0] = st
        w = rows[0:1] * jnp.exp(total - rows[1:2])    # dt·exp(T − cs)
        xw = x_ref[0, i].astype(jnp.float32) * w      # [P, Q]
        st_ref[i] = st * jnp.exp(total) + _mm(xw, Bt, _NT, dtype)
        return carry

    jax.lax.fori_loop(0, heads, head, 0, unroll=True)


def _grads_kernel(x_ref, dy_ref, r_ref, D_ref, B_ref, C_ref, s0_ref,
                  dfin_ref,                                         # in
                  dx_ref, dr_ref, dB_ref, dC_ref,                   # out
                  ds_ref, g_ref, dg_ref, db_ref, dc_ref,            # scratch
                  *, heads: int, rep: int):
    """One chunk (chunks in reverse) of one B/C group, ``heads`` of its
    heads, with positions on lanes: x, dy [P, Q], B, C [N, Q]. Per head:
    dx; and, as rows, the direct gradient of dt, the gradient of the
    in-chunk cumulative sum cs of dt·A, and Σ_p dy·x (for D). Per chunk
    and group: C·Bᵀ once, the heads' score gradients summed in fp32 VMEM
    before the dB and dC products within the chunk. The state's gradient
    dS [P, N] is carried across chunks in fp32 VMEM."""
    gi, c, hj = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first = hj * heads                    # the block's first head in group
    Bt, Ct = B_ref[0, 0], C_ref[0, 0]                 # [N, Q]
    dtype = Bt.dtype
    Q = Bt.shape[1]

    @pl.when(hj == 0)
    def _group_start():
        g_ref[...] = _mm(Ct, Bt, _TN, dtype)          # G = C·Bᵀ [Q, Q]
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    @pl.when(c == 0)
    def _last_chunk():
        ds_ref[pl.ds(first, heads)] = dfin_ref[0].astype(jnp.float32)

    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    which = jax.lax.broadcasted_iota(jnp.int32, (3, Q), 0)
    Bf, Cf = Bt.astype(jnp.float32), Ct.astype(jnp.float32)

    def head(i, carry):
        x, dy = x_ref[0, i], dy_ref[0, i]             # [P, Q]
        xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
        rows = r_ref[0, i]
        dt, cs = rows[0:1], rows[1:2]                 # [1, Q]
        total = _chunk_total(cs)
        s0, ds1 = s0_ref[0, i, 0], ds_ref[first + i]  # [P, N] fp32
        D = D_ref[gi * rep + first + i]

        # intra-chunk: y = (G ⊙ L)(dt·x), L[q,k] = exp(cs_q − cs_k), k <= q
        L = jnp.where(causal, jnp.exp(cs.T - cs), 0.0)
        M = g_ref[...] * L
        dM = _mm(dy, xf * dt, _TN, dtype)             # dy·(dt·x)ᵀ [Q, Q]
        dxd = _mm(dy, M, _NN, dtype)                  # (Mᵀ·dy)ᵀ [P, Q]
        dg_ref[...] += dM * L
        R = dM * M                                    # ∂/∂(cs_q − cs_k)
        dcs = -jnp.sum(R, axis=0, keepdims=True)      # [1, Q]
        ddt = jnp.sum(dxd * xf, axis=0, keepdims=True)
        dx = dxd * dt + D * dyf

        # inter-chunk: y += exp(cs)·(C S0ᵀ)
        e = jnp.exp(cs)
        Sdy = _mm(s0, dy, _TN, dtype)                 # (dy·S0)ᵀ [N, Q]
        dc_ref[...] += Sdy * e
        dcs = dcs + e * jnp.sum(Sdy * Cf, axis=0, keepdims=True)
        ds0 = _mm(dyf * e, Ct, _NT, dtype)            # [P, N]

        # state: S1 = S0·exp(T) + Σ_k dt_k·exp(T − cs_k)·x_k B_kᵀ
        decay = jnp.exp(total - cs)
        w = dt * decay
        xdS = _mm(ds1, x, _TN, dtype)                 # (x·dS1)ᵀ [N, Q]
        db_ref[...] += xdS * w
        dx = dx + _mm(ds1, Bt, _NN, dtype) * w
        dw = jnp.sum(Bf * xdS, axis=0, keepdims=True)
        ddt = ddt + dw * decay
        d_total = jnp.sum(dw * w) + jnp.exp(total) * jnp.sum(ds1 * s0)
        ds_ref[first + i] = ds0 + ds1 * jnp.exp(total)
        # T = cs[Q-1]; the row sums of R are the cs_q side
        dcs = (dcs - dw * w + jnp.sum(R, axis=1, keepdims=True).T
               + jnp.where(at_end, d_total, 0.0))

        dx_ref[0, i] = dx.astype(dx_ref.dtype)
        dxy = jnp.sum(dyf * xf, axis=0, keepdims=True)
        dr_ref[0, i] = jnp.where(which == 0, ddt,
                                 jnp.where(which == 1, dcs, dxy))
        return carry

    jax.lax.fori_loop(0, heads, head, 0, unroll=True)

    @pl.when(hj == pl.num_programs(3) - 1)
    def _group_end():
        dg = dg_ref[...]
        dC_ref[0, 0] = (dc_ref[...] + _mm(Bt, dg, _NT, dtype)).astype(
            dC_ref.dtype)
        dB_ref[0, 0] = (db_ref[...] + _mm(Ct, dg, _NN, dtype)).astype(
            dB_ref.dtype)


def _ssd_backward(x, dt, A, B, C, D, dy, dfinal, chunk, interpret):
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nch, rep = l // chunk, h // g
    heads = _heads_per_block(rep, chunk, p, n, jnp.dtype(x.dtype).itemsize)
    nhb = rep // heads
    f32 = jnp.float32
    # tri[k, q] = k <= q: in-chunk cumulative sums as exact products
    tri = jnp.triu(jnp.ones((chunk, chunk), f32))

    # dt and its in-chunk cumulative sum cs of dt·A, as rows [b, h, 2, l]
    dt_t = dt.astype(f32).transpose(0, 2, 1)                  # [b, h, l]
    a = (dt_t * A.astype(f32)[None, :, None]).reshape(b, h, nch, chunk)
    cs = jnp.einsum("bhck,kq->bhcq", a, tri, precision=_HIGHEST)
    rows = jnp.stack([dt_t, cs.reshape(b, h, l)], axis=2)
    # positions on lanes: x, dy [b, h, p, l]; B, C [b, g, n, l]
    xt = x.transpose(0, 2, 3, 1)
    dyt = dy.astype(x.dtype).transpose(0, 2, 3, 1)
    Bt, Ct = B.transpose(0, 2, 3, 1), C.transpose(0, 2, 3, 1)

    def heads_fwd(bi, gi, hj, ci):
        return (bi, gi * nhb + hj, 0, ci)

    s0 = pl.pallas_call(
        functools.partial(_states_kernel, heads=heads),
        grid=(b, g, nhb, nch),
        in_specs=[
            pl.BlockSpec((1, heads, p, chunk), heads_fwd),
            pl.BlockSpec((1, heads, 2, chunk), heads_fwd),
            pl.BlockSpec((1, 1, n, chunk),
                         lambda bi, gi, hj, ci: (bi, gi, 0, ci)),
        ],
        out_specs=pl.BlockSpec(
            (1, heads, 1, p, n),
            lambda bi, gi, hj, ci: (bi, gi * nhb + hj, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nch, p, n), f32),
        scratch_shapes=[pltpu.VMEM((heads, p, n), f32)],
        interpret=interpret,
        name="ssd_scan_bwd_states",
    )(xt, rows, Bt)

    def heads_rev(bi, gi, ci, hj):                 # chunks in reverse
        return (bi, gi * nhb + hj, 0, nch - 1 - ci)

    def group_rev(bi, gi, ci, hj):
        return (bi, gi, 0, nch - 1 - ci)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    dx, dr, dB, dC = pl.pallas_call(
        functools.partial(_grads_kernel, heads=heads, rep=rep),
        grid=(b, g, nch, nhb),
        in_specs=[
            pl.BlockSpec((1, heads, p, chunk), heads_rev),
            pl.BlockSpec((1, heads, p, chunk), heads_rev),
            pl.BlockSpec((1, heads, 2, chunk), heads_rev),
            smem,
            pl.BlockSpec((1, 1, n, chunk), group_rev),
            pl.BlockSpec((1, 1, n, chunk), group_rev),
            pl.BlockSpec((1, heads, 1, p, n),
                         lambda bi, gi, ci, hj: (bi, gi * nhb + hj,
                                                 nch - 1 - ci, 0, 0)),
            pl.BlockSpec((1, heads, p, n),
                         lambda bi, gi, ci, hj: (bi, gi * nhb + hj, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, p, chunk), heads_rev),
            pl.BlockSpec((1, heads, 3, chunk), heads_rev),
            pl.BlockSpec((1, 1, n, chunk), group_rev),
            pl.BlockSpec((1, 1, n, chunk), group_rev),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, p, l), x.dtype),
            jax.ShapeDtypeStruct((b, h, 3, l), f32),
            jax.ShapeDtypeStruct((b, g, n, l), B.dtype),
            jax.ShapeDtypeStruct((b, g, n, l), C.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((rep, p, n), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((n, chunk), f32),
                        pltpu.VMEM((n, chunk), f32)],
        interpret=interpret,
        name="ssd_scan_bwd_grads",
    )(xt, dyt, rows, D.astype(f32), Bt, Ct, s0, dfinal)

    # cs is a cumulative sum of dt·A within each chunk: its gradient
    # reaches dt·A as the reverse cumulative sum
    da = jnp.einsum("bhcq,kq->bhck", dr[:, :, 1].reshape(b, h, nch, chunk),
                    tri, precision=_HIGHEST).reshape(b, h, l)
    ddt = dr[:, :, 0] + A.astype(f32)[None, :, None] * da
    return (dx.transpose(0, 3, 1, 2),
            ddt.transpose(0, 2, 1).astype(dt.dtype),
            jnp.sum(dt_t * da, axis=(0, 2)).astype(A.dtype),
            dB.transpose(0, 3, 1, 2),
            dC.transpose(0, 3, 1, 2),
            jnp.sum(dr[:, :, 2], axis=(0, 2)).astype(D.dtype))


def _ssd_bwd(chunk, interpret, res, g):
    dy, dfinal = g
    return _ssd_backward(*res, dy, dfinal, chunk, interpret)


_ssd = jax.custom_vjp(_ssd_forward, nondiff_argnums=(6, 7))
_ssd.defvjp(_ssd_fwd, _ssd_bwd)
