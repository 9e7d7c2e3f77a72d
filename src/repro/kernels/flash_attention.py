"""Flash attention as a Pallas TPU kernel.

Design (TPU-native, not a CUDA port):
  * grid = (batch·q_heads, Sq/blk_q, Skv/blk_kv); the KV dimension is the
    innermost (sequential on TPU), carrying the online-softmax state
    (m, l, acc) in fp32 VMEM scratch across KV steps.
  * BlockSpecs tile Q as (blk_q, head_dim) and K/V as (blk_kv, head_dim)
    in VMEM; head_dim is the MXU lane dim (128-multiples for the assigned
    archs), blk defaults to 128 rows — one MXU tile per dot.
  * GQA is pure index arithmetic: the K/V block index-map folds the
    q-head → kv-head mapping, so no KV replication is materialized.
  * causal / sliding-window / ring-buffer-decode masking is computed from
    *position vectors* (q_pos, kv_pos) — the same mechanism the model uses
    for its ring caches — not from row indices, so one kernel serves
    train, prefill and decode. Mosaic tiles a 1-D int32 array differently
    from XLA, so positions enter 2-D: q_pos as a [Sq, 1] column, kv_pos
    as a [1, Skv] row (blocks (blk_q, 1) and (1, blk_kv)), and the
    running max / denominator are lane-replicated (blk_q, 128) scratch,
    as in the upstream Pallas TPU flash kernel.
  * the backward pass recomputes attention with the blockwise jnp path
    (``models.attention.attend_blockwise``) and differentiates that: the
    kernel is forward-only, and pallas_call has no transpose rule.
  * logit softcap (gemma2) and scale overrides are static params fused
    into the score computation.

Validated against ``ref.attention_ref`` in interpret mode (CPU) over a
shape/dtype sweep in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

from repro.models.attention import AttnSpec, attend_blockwise

NEG_INF = -2.3819763e38
LANES = 128


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kvpos_ref,   # inputs
            o_ref,                                      # output
            m_ref, l_ref, acc_ref,                      # scratch
            *, scale: float, causal: bool, window: int, softcap: float,
            n_kv_blocks: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0].astype(jnp.float32)                  # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    qp = qpos_ref[...]                                # [bq, 1]
    kp = kvpos_ref[...]                               # [1, bk]
    ok = jnp.broadcast_to(kp < 2 ** 30, s.shape)      # pad sentinel
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > (qp - window)
    s = jnp.where(ok, s, NEG_INF)

    # m/l hold one value per row, replicated over the 128 lanes
    m_prev = m_ref[...]                               # [bq, 128]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha[:, :1] +
                    jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)


def flash_attention(q, k, v, q_pos, kv_pos, spec: AttnSpec, *,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B,Sq,Hq,hd]; k,v: [B,Skv,Hkv,hd]; q_pos [Sq]; kv_pos [Skv].

    Returns [B,Sq,Hq,hd]. Sq/Skv are padded to block multiples internally
    (padded kv positions get +inf -> masked by causality).
    """
    return _flash(q, k, v, q_pos, kv_pos, spec, block_q, block_kv, interpret)


def _flash_forward(q, k, v, q_pos, kv_pos, spec: AttnSpec, block_q: int,
                   block_kv: int, interpret: bool) -> jax.Array:
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = spec.scale or 1.0 / math.sqrt(hd)
    block_q = min(block_q, max(Sq, 8))
    block_kv = min(block_kv, max(Skv, 8))

    pad_q = (-Sq) % block_q
    pad_kv = (-Skv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=2 ** 30 - 1)
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad_kv), constant_values=2 ** 30)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    nq, nk = Sq_p // block_q, Skv_p // block_kv

    # [B,S,H,hd] -> [B*H, S, hd] rows; kv head folded via index map
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq_p, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv_p, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv_p, hd)

    kernel = functools.partial(
        _kernel, scale=scale, causal=spec.causal, window=spec.window,
        softcap=spec.logit_softcap, n_kv_blocks=nk)

    def kv_index(h, iq, ik, G=G, Hkv=Hkv):
        # q row h = b*Hq + hq  ->  kv row = b*Hkv + hq//G
        return ((h // (G * Hkv)) * Hkv + (h % (G * Hkv)) // G, ik, 0)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, block_kv, hd), kv_index),
            pl.BlockSpec((1, block_kv, hd), kv_index),
            pl.BlockSpec((block_q, 1), lambda h, iq, ik: (iq, 0)),
            pl.BlockSpec((1, block_kv), lambda h, iq, ik: (0, ik)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, hd), jnp.float32),     # acc
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf, q_pos.astype(jnp.int32).reshape(Sq_p, 1),
      kv_pos.astype(jnp.int32).reshape(1, Skv_p))

    out = out.reshape(B, Hq, Sq_p, hd).transpose(0, 2, 1, 3)
    return out[:, :Sq]


def _flash_fwd(q, k, v, q_pos, kv_pos, spec, block_q, block_kv, interpret):
    out = _flash_forward(q, k, v, q_pos, kv_pos, spec, block_q, block_kv,
                         interpret)
    return out, (q, k, v, q_pos, kv_pos)


def _flash_bwd(spec, block_q, block_kv, interpret, res, g):
    q, k, v, q_pos, kv_pos = res
    _, vjp = jax.vjp(
        lambda q, k, v: attend_blockwise(q, k, v, q_pos, kv_pos, spec,
                                         block=block_kv), q, k, v)
    return (*vjp(g), None, None)


_flash = jax.custom_vjp(_flash_forward, nondiff_argnums=(5, 6, 7, 8))
_flash.defvjp(_flash_fwd, _flash_bwd)
