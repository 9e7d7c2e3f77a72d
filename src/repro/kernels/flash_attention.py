"""Flash attention as Pallas TPU kernels: a forward, and a
FlashAttention-2 backward behind the forward's ``custom_vjp``.

Forward (``flash_fwd``; TPU-native, not a CUDA port):
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
  * logit softcap (gemma2) and scale overrides are static params fused
    into the score computation (``_logits``, shared with the backward).

Backward (FlashAttention-2 structure; residuals q, k, v, o). Three
kernels, each on a grid over batch·kv_heads whose steps loop over the G
query heads of that KV head, so K/V tiles serve all G and dK/dV are
written once per KV head:
  * ``flash_bwd_lse`` (q block, kv block; kv innermost) recomputes each
    query row's log-sum-exp: one QKᵀ over the attendable blocks. delta =
    rowsum(dO·O) is a jnp reduction.
  * ``flash_bwd_dq`` (q block, kv block; kv innermost) recomputes P from
    q, k and the log-sum-exp, and accumulates dQ += dS·K in fp32 VMEM,
    dS = P ∘ (dO·Vᵀ − delta).
  * ``flash_bwd_dkv`` (kv block, q block; q innermost) accumulates
    dV += Pᵀ·dO and dK += dSᵀ·Q.
  * A query row's statistics are lane rows [1, blk_q]: ``flash_bwd_lse``
    and ``flash_bwd_dkv`` score K·Qᵀ, and ``flash_bwd_dq`` scores Q·Kᵀ,
    turning the rows into lane-replicated columns once per q block.
  * Block pairs the mask rules out entirely are skipped: per-block
    position bounds enter as scalar prefetch, ``pl.when`` skips the
    compute and the index maps stay on a block already fetched. Pairs the
    mask leaves whole skip the mask. Block sizes follow the sequence
    lengths.
  * Matmul operands are the inputs' dtype (P and dS cast to it), with
    fp32 accumulation; softmax, log-sum-exp and delta are fp32.

Validated against ``ref.attention_ref`` and its gradients in interpret
mode (CPU) over a shape/dtype sweep in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

from repro.models.attention import PAD_POS, AttnSpec

NEG_INF = -2.3819763e38
LANES = 128
NT = (((1,), (1,)), ((), ()))        # a·bᵀ
NN = (((1,), (0,)), ((), ()))        # a·b


def _logits(a, b, qp, kp, *, causal: bool, window: int, softcap: float,
            masked: bool = True):
    """Scores a·bᵀ in fp32, logit-capped, and NEG_INF where the mask rules
    a pair out (unless ``masked`` is false: a block the mask leaves
    whole). q·kᵀ takes qp a column and kp a row, k·qᵀ the reverse; the
    mask broadcasts either way. Padding slots (kv position ``PAD_POS``)
    are masked unconditionally."""
    s = jax.lax.dot_general(a, b, NT, preferred_element_type=jnp.float32)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    if not masked:
        return s
    ok = jnp.broadcast_to(kp < PAD_POS, s.shape)
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > (qp - window)
    return jnp.where(ok, s, NEG_INF)


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
    s = _logits(q, k, qpos_ref[...], kvpos_ref[...], causal=causal,
                window=window, softcap=softcap)       # [bq, bk]

    # m/l hold one value per row, replicated over the 128 lanes
    m_prev = m_ref[...]                               # [bq, 128]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha[:, :1] +
                    jax.lax.dot_general(p, v, NN))
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
    (padded kv positions get +inf -> masked by causality). The blocks
    set the forward's tiling; the backward tiles by the shapes.
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
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=PAD_POS - 1)
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad_kv), constant_values=PAD_POS)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    nq, nk = Sq_p // block_q, Skv_p // block_kv

    # [B,S,H,hd] -> [B*H, S, hd] rows; kv head folded via index map
    qf, kf, vf = _rows(q), _rows(k), _rows(v)

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

    return _unrows(out, B)[:, :Sq]


def _rows(x):
    """[B,S,H,hd] -> [B*H, S, hd]."""
    B, S, H, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)


def _unrows(x, B):
    """[B*H, S, hd] -> [B,S,H,hd]."""
    BH, S, hd = x.shape
    return x.reshape(B, BH // B, S, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_block(n: int) -> int:
    """Rows of a backward block over a sequence of n: 512, or all of a
    shorter one, in whole lane tiles. On a v5e, 512 took a layer's
    backward 1.6x less time than 256 (12 x 2048, hd 64)."""
    return min(512, -(-n // LANES) * LANES)


def _block_info(q_pos, kv_pos, bq: int, bk: int, spec: AttnSpec):
    """Scalar-prefetch tables for block skipping, both int32.

    qi = [qmin | qmax | kv_lo | kv_hi] over the nq query blocks and
    ki = [kmin | kmax | kpad | q_lo | q_hi] over the nk kv blocks: the
    position bounds of each block (kv bounds over its real slots), whether
    a kv block holds padding, and for each block the first and last block
    of the other side that it shares an attendable pair with (where the
    index maps clamp, so that a skipped step fetches nothing new).
    """
    nq, nk = q_pos.shape[0] // bq, kv_pos.shape[0] // bk
    qb, kb = q_pos.reshape(nq, bq), kv_pos.reshape(nk, bk)
    real = kb < PAD_POS
    qmin, qmax = qb.min(1), qb.max(1)
    kmin = jnp.where(real, kb, PAD_POS).min(1)
    kmax = jnp.where(real, kb, -PAD_POS).max(1)
    kpad = (~real).any(1).astype(jnp.int32)
    live = _live(qmin[:, None], qmax[:, None], kmin[None], kmax[None], spec)
    live = jnp.broadcast_to(live, (nq, nk))

    def first_last(m):                      # [n, n_other] -> lo, hi
        n_other = m.shape[1]
        lo = jnp.argmax(m, axis=1)
        hi = n_other - 1 - jnp.argmax(m[:, ::-1], axis=1)
        return lo, jnp.maximum(hi, lo)

    kv_lo, kv_hi = first_last(live)
    q_lo, q_hi = first_last(live.T)
    qi = jnp.concatenate([qmin, qmax, kv_lo, kv_hi]).astype(jnp.int32)
    ki = jnp.concatenate([kmin, kmax, kpad, q_lo, q_hi]).astype(jnp.int32)
    return qi, ki


def _live(qmin, qmax, kmin, kmax, spec: AttnSpec):
    """Whether a (q block, kv block) pair holds an attendable pair."""
    ok = kmin < PAD_POS
    if spec.causal:
        ok &= kmin <= qmax
    if spec.window:
        ok &= kmax > qmin - spec.window
    return ok


def _whole(qmin, qmax, kmin, kmax, kpad, spec: AttnSpec):
    """Whether the mask leaves every pair of a block pair attendable."""
    ok = kpad == 0
    if spec.causal:
        ok &= kmax <= qmin
    if spec.window:
        ok &= kmin > qmax - spec.window
    return ok


class _Blocks:
    """Reads the prefetched tables inside a kernel or an index map."""

    def __init__(self, qi, ki, nq: int, nk: int, spec: AttnSpec):
        self.qi, self.ki, self.nq, self.nk, self.spec = qi, ki, nq, nk, spec
        self.skip = bool(spec.causal or spec.window)

    def run(self, iq, ik, body):
        """``body(masked)`` on block pair (iq, ik): skipped where the mask
        rules the pair out, unmasked where it leaves the pair whole (on
        a v5e the mask took a tenth of the log-sum-exp pass's time)."""
        qi, ki, nq, nk = self.qi, self.ki, self.nq, self.nk
        bounds = qi[iq], qi[nq + iq], ki[ik], ki[nk + ik]
        live = _live(*bounds, self.spec) if self.skip else True
        whole = _whole(*bounds, ki[2 * nk + ik], self.spec)
        pl.when(jnp.logical_and(live, whole))(lambda: body(False))
        pl.when(jnp.logical_and(live, ~whole))(lambda: body(True))

    def kv_fetch(self, iq, ik):
        """The kv block to fetch at step (iq, ik) of a kv-inner grid."""
        if not self.skip:
            return ik
        nq = self.nq
        return jnp.minimum(jnp.maximum(ik, self.qi[2 * nq + iq]),
                           self.qi[3 * nq + iq])

    def q_fetch(self, ik, iq):
        """The q block to fetch at step (ik, iq) of a q-inner grid."""
        if not self.skip:
            return iq
        nk = self.nk
        return jnp.minimum(jnp.maximum(iq, self.ki[3 * nk + ik]),
                           self.ki[4 * nk + ik])


def _lse_kernel(qi_ref, ki_ref,                          # scalar prefetch
                q_ref, k_ref, qpos_ref, kvpos_ref,       # inputs
                lse_ref,                                 # output
                m_ref, l_ref,                            # scratch
                *, spec: AttnSpec, nq: int, nk: int, G: int):
    iq, ik = pl.program_id(1), pl.program_id(2)
    blocks = _Blocks(qi_ref, ki_ref, nq, nk, spec)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(masked):
        k = k_ref[0]                                            # [bk, hd]
        for g in range(G):                  # the query heads of this kv head
            s = _logits(k, q_ref[0, g], qpos_ref[...], kvpos_ref[...],
                        causal=spec.causal, window=spec.window,
                        softcap=spec.logit_softcap, masked=masked)  # [bk, bq]
            m_prev = m_ref[g]                                   # [1, bq]
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            l_ref[g] = (l_ref[g] * jnp.exp(m_prev - m_new)
                        + jnp.exp(s - m_new).sum(axis=0, keepdims=True))
            m_ref[g] = m_new

    blocks.run(iq, ik, body)

    @pl.when(ik == nk - 1)
    def _emit():
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])


def _dq_kernel(qi_ref, ki_ref,                                # prefetch
               do_ref, lse_ref, delta_ref, q_ref, k_ref, v_ref,
               qpos_ref, kvpos_ref,                           # inputs
               dq_ref,                                        # output
               lse_col, delta_col, acc_ref,                   # scratch
               *, scale, spec: AttnSpec, nq: int, nk: int, G: int):
    iq, ik = pl.program_id(1), pl.program_id(2)
    blocks = _Blocks(qi_ref, ki_ref, nq, nk, spec)
    bq = acc_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for g in range(G):          # rows' statistics as replicated columns
            lse_col[g] = jnp.broadcast_to(lse_ref[0, g], (LANES, bq)).T
            delta_col[g] = jnp.broadcast_to(delta_ref[0, g], (LANES, bq)).T

    def body(masked):
        k, v = k_ref[0], v_ref[0]                               # [bk, hd]
        for g in range(G):
            s = _logits(q_ref[0, g], k, qpos_ref[...], kvpos_ref[...],
                        causal=spec.causal, window=spec.window,
                        softcap=spec.logit_softcap, masked=masked)  # [bq, bk]
            p = jnp.exp(s - lse_col[g][:, :1])
            dp = jax.lax.dot_general(do_ref[0, g], v, NT,
                                     preferred_element_type=jnp.float32)
            ds = _dscores(p, dp, delta_col[g][:, :1], s, spec.logit_softcap)
            acc_ref[g] += jax.lax.dot_general(
                ds.astype(k.dtype), k, NN,
                preferred_element_type=jnp.float32)

    blocks.run(iq, ik, body)

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, ki_ref,                               # prefetch
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                qpos_ref, kvpos_ref,                          # inputs
                dk_ref, dv_ref,                               # outputs
                dk_acc, dv_acc,                               # scratch
                *, spec: AttnSpec, nq: int, nk: int, G: int):
    ik, iq = pl.program_id(1), pl.program_id(2)
    blocks = _Blocks(qi_ref, ki_ref, nq, nk, spec)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        k, v = k_ref[0], v_ref[0]                               # [bk, hd]
        for g in range(G):                  # the query heads of this kv head
            q, do = q_ref[0, g], do_ref[0, g]                  # [bq, hd]
            s = _logits(k, q, qpos_ref[...], kvpos_ref[...],
                        causal=spec.causal, window=spec.window,
                        softcap=spec.logit_softcap, masked=masked)  # [bk, bq]
            p = jnp.exp(s - lse_ref[0, g])
            dv_acc[...] += jax.lax.dot_general(
                p.astype(do.dtype), do, NN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, NT,
                                     preferred_element_type=jnp.float32)
            ds = _dscores(p, dp, delta_ref[0, g], s, spec.logit_softcap)
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(q.dtype), q, NN,
                preferred_element_type=jnp.float32)

    blocks.run(iq, ik, body)

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dscores(p, dp, delta, s, softcap):
    """dS = P ∘ (dP − delta), through the logit cap where there is one
    (masked scores are NEG_INF there, and their P is 0)."""
    ds = p * (dp - delta)
    if softcap:
        ds = ds * (1.0 - jnp.minimum(jnp.square(s / softcap), 1.0))
    return ds


def _flash_backward(q, k, v, o, q_pos, kv_pos, do, spec: AttnSpec,
                    interpret: bool):
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = spec.scale or 1.0 / math.sqrt(hd)
    bq, bk = _bwd_block(Sq), _bwd_block(Skv)
    pad_q, pad_kv = (-Sq) % bq, (-Skv) % bk
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    nq, nk = Sq_p // bq, Skv_p // bk

    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    seq_q = ((0, 0), (0, pad_q), (0, 0), (0, 0))
    seq_kv = ((0, 0), (0, pad_kv), (0, 0), (0, 0))
    # the softmax scale folded into q (in q's dtype, in the same pass as
    # its transpose): scores come out scaled and dK = dSᵀ·(scale·Q)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qf, dof = _rows(jnp.pad(q, seq_q)), _rows(jnp.pad(do, seq_q))
    kf, vf = _rows(jnp.pad(k, seq_kv)), _rows(jnp.pad(v, seq_kv))
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q))).reshape(
        B * Hq, 1, Sq_p)
    q_pos = jnp.pad(q_pos.astype(jnp.int32), (0, pad_q),
                    constant_values=PAD_POS - 1)
    kv_pos = jnp.pad(kv_pos.astype(jnp.int32), (0, pad_kv),
                     constant_values=PAD_POS)
    qi, ki = _block_info(q_pos, kv_pos, bq, bk, spec)
    qp_row, qp_col = q_pos.reshape(1, Sq_p), q_pos.reshape(Sq_p, 1)
    kp_row, kp_col = kv_pos.reshape(1, Skv_p), kv_pos.reshape(Skv_p, 1)
    kw = dict(spec=spec, nq=nq, nk=nk, G=G)
    # a kv head's G query heads per step outgrow the default 16 MiB of
    # scoped VMEM at G 8 and head dim 128 (qwen2.5-3b's dQ: 16.5 MiB)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=32 * 2 ** 20)
    # every grid runs over kv rows b*Hkv + hkv; the G query heads of a kv
    # head sit side by side in each q-side block
    q4 = qf.reshape(B * Hkv, G, Sq_p, hd)
    do4 = dof.reshape(B * Hkv, G, Sq_p, hd)
    delta4 = delta.reshape(B * Hkv, G, 1, Sq_p)

    def blocks(qi, ki):
        return _Blocks(qi, ki, nq, nk, spec)

    # kv-inner grids over q blocks: the log-sum-exp, then dQ
    def at_q(h, iq, ik, qi, ki):
        return (h, 0, iq, 0)

    def at_q_stat(h, iq, ik, qi, ki):
        return (h, 0, 0, iq)

    def at_kv(h, iq, ik, qi, ki):
        return (h, blocks(qi, ki).kv_fetch(iq, ik), 0)

    lse = pl.pallas_call(
        functools.partial(_lse_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, nq, nk),
            in_specs=[
                pl.BlockSpec((1, G, bq, hd), at_q),
                pl.BlockSpec((1, bk, hd), at_kv),
                pl.BlockSpec((1, bq), lambda h, iq, ik, qi, ki: (0, iq)),
                pl.BlockSpec((bk, 1), lambda h, iq, ik, qi, ki:
                             (blocks(qi, ki).kv_fetch(iq, ik), 0)),
            ],
            out_specs=pl.BlockSpec((1, G, 1, bq), at_q_stat),
            scratch_shapes=[pltpu.VMEM((G, 1, bq), jnp.float32),   # m
                            pltpu.VMEM((G, 1, bq), jnp.float32)],  # l
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, 1, Sq_p), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_lse",
    )(qi, ki, q4, kf, qp_row, kp_col)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, nq, nk),
            in_specs=[
                pl.BlockSpec((1, G, bq, hd), at_q),       # dO first: not
                pl.BlockSpec((1, G, 1, bq), at_q_stat),   # a forward's
                pl.BlockSpec((1, G, 1, bq), at_q_stat),   # q, k, v
                pl.BlockSpec((1, G, bq, hd), at_q),
                pl.BlockSpec((1, bk, hd), at_kv),
                pl.BlockSpec((1, bk, hd), at_kv),
                pl.BlockSpec((bq, 1), lambda h, iq, ik, qi, ki: (iq, 0)),
                pl.BlockSpec((1, bk), lambda h, iq, ik, qi, ki:
                             (0, blocks(qi, ki).kv_fetch(iq, ik))),
            ],
            out_specs=pl.BlockSpec((1, G, bq, hd), at_q),
            scratch_shapes=[pltpu.VMEM((G, bq, LANES), jnp.float32),  # lse
                            pltpu.VMEM((G, bq, LANES), jnp.float32),  # delta
                            pltpu.VMEM((G, bq, hd), jnp.float32)],    # dQ
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Sq_p, hd), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qi, ki, do4, lse, delta4, q4, kf, vf, qp_col, kp_row)

    # q-inner grid over kv blocks: dK and dV
    def at_heads(h, ik, iq, qi, ki):
        return (h, 0, blocks(qi, ki).q_fetch(ik, iq), 0)

    def at_heads_stat(h, ik, iq, qi, ki):
        return (h, 0, 0, blocks(qi, ki).q_fetch(ik, iq))

    def at_kv_row(h, ik, iq, qi, ki):
        return (h, ik, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, nk, nq),
            in_specs=[
                pl.BlockSpec((1, G, bq, hd), at_heads),
                pl.BlockSpec((1, bk, hd), at_kv_row),
                pl.BlockSpec((1, bk, hd), at_kv_row),
                pl.BlockSpec((1, G, bq, hd), at_heads),
                pl.BlockSpec((1, G, 1, bq), at_heads_stat),
                pl.BlockSpec((1, G, 1, bq), at_heads_stat),
                pl.BlockSpec((1, bq), lambda h, ik, iq, qi, ki:
                             (0, blocks(qi, ki).q_fetch(ik, iq))),
                pl.BlockSpec((bk, 1), lambda h, ik, iq, qi, ki: (ik, 0)),
            ],
            out_specs=[pl.BlockSpec((1, bk, hd), at_kv_row),
                       pl.BlockSpec((1, bk, hd), at_kv_row)],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),    # dK
                            pltpu.VMEM((bk, hd), jnp.float32)],   # dV
        ),
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, Skv_p, hd), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, Skv_p, hd), v.dtype)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qi, ki, q4, kf, vf, do4, lse, delta4, qp_row, kp_col)

    dq = dq.reshape(B * Hq, Sq_p, hd)
    return (_unrows(dq, B)[:, :Sq], _unrows(dk, B)[:, :Skv],
            _unrows(dv, B)[:, :Skv])


def _flash_fwd(q, k, v, q_pos, kv_pos, spec, block_q, block_kv, interpret):
    out = _flash_forward(q, k, v, q_pos, kv_pos, spec, block_q, block_kv,
                         interpret)
    return out, (q, k, v, out, q_pos, kv_pos)


def _flash_bwd(spec, block_q, block_kv, interpret, res, g):
    q, k, v, o, q_pos, kv_pos = res
    return (*_flash_backward(q, k, v, o, q_pos, kv_pos, g, spec, interpret),
            None, None)


_flash = jax.custom_vjp(_flash_forward, nondiff_argnums=(5, 6, 7, 8))
_flash.defvjp(_flash_fwd, _flash_bwd)
