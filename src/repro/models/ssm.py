"""Mamba2 block (SSD — state-space duality), chunked scan + recurrent decode.

Shapes follow the Mamba2 paper: heads H = expand·d_model / head_dim P,
state size N, B/C shared across ``n_groups`` G. The chunked ("SSD") form
computes, per chunk of length Q:

  intra-chunk:  Y_intra = (L ⊙ (C Bᵀ)) X           (attention-like, MXU)
  inter-chunk:  states  = (decay ⊙ X)ᵀ B           carried recurrently
                Y_inter = decay_in · C · states_prev

Training/prefill use the chunked form (``repro.kernels.ops.ssd_chunked`` —
Pallas on TPU, jnp reference elsewhere). Decode is the O(1)-per-token
recurrence on the carried state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.models.layers import Param, Params, dense, init_dense, make_param


# ---------------------------------------------------------------------------
# Reference chunked SSD (pure jnp; oracle for the Pallas kernel, and the
# recompute its custom VJP differentiates)
# ---------------------------------------------------------------------------

def segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k in (j, i]} x[..., k].

    Lower-triangular; -inf above the diagonal. x: [..., T] -> [..., T, T].
    """
    T = x.shape[-1]
    csum = jnp.cumsum(x, axis=-1)
    diff = csum[..., :, None] - csum[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_reference(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                  C: jax.Array, D: jax.Array, chunk: int = 64,
                  h0: Optional[jax.Array] = None,
                  return_state: bool = False):
    """Chunked SSD scan.

    x:  [b, l, h, p]    inputs (already gated/projected)
    dt: [b, l, h]       softplus'd step sizes
    A:  [h]             negative decay rates (A < 0)
    B:  [b, l, g, n]    input maps (g groups broadcast over h)
    C:  [b, l, g, n]    output maps
    D:  [h]             skip connection
    h0: [b, h, p, n]    optional initial state
    Returns y [b, l, h, p] (and final state if return_state).
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0, (l, chunk)
    nch = l // chunk
    rep = h // g
    dtA = dt * A[None, None, :]                          # [b, l, h]

    xc = x.reshape(b, nch, chunk, h, p)
    dtc = dt.reshape(b, nch, chunk, h)
    dtAc = dtA.reshape(b, nch, chunk, h)
    Bc = B.reshape(b, nch, chunk, g, n)
    Cc = C.reshape(b, nch, chunk, g, n)
    Bh = jnp.repeat(Bc, rep, axis=3)                     # [b, c, q, h, n]
    Ch = jnp.repeat(Cc, rep, axis=3)

    # Every decay below is the exp of a sum of dt·A <= 0, formed as a sum
    # and never as a quotient of exponentials: each lies in [0, 1] and its
    # gradient exp(s)·g stays finite however far exp(s) underflows.

    # --- intra-chunk (quadratic in chunk len, MXU-friendly) ---------------
    # L[q, k] = exp(sum_{k<j<=q} dtA) below the diagonal; exp(-inf) = 0 above
    Ls = jnp.exp(segsum(dtAc.transpose(0, 1, 3, 2)))     # [b, c, h, q, q]
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Ch, Bh) * Ls
    y_intra = jnp.einsum("bchqk,bckh,bckhp->bcqhp", scores, dtc, xc)

    # --- chunk states ------------------------------------------------------
    # decay from position q to the end of its chunk, exp(sum_{k>q} dtA):
    # the inclusive reverse cumulative sum shifted up by one position
    rev = dtAc[..., ::-1, :].cumsum(axis=2)[..., ::-1, :]  # sum_{k>=q}
    after = jnp.pad(rev[:, :, 1:], ((0, 0), (0, 0), (0, 1), (0, 0)))
    decay_states = jnp.exp(after)                        # [b, c, q, h]
    states = jnp.einsum("bcqhn,bcqh,bcqh,bcqhp->bchpn",
                        Bh, dtc, decay_states, xc)       # [b, c, h, p, n]

    # --- inter-chunk recurrence (scan over chunks) -------------------------
    chunk_decay = jnp.exp(dtAc.sum(axis=2))              # [b, c, h]

    def step(carry, xs):
        st, cd = xs
        new = carry * cd[..., None, None] + st
        return new, carry                                 # emit state *before*

    init = h0 if h0 is not None else jnp.zeros((b, h, p, n), x.dtype)
    final, prev_states = jax.lax.scan(
        step, init.astype(jnp.float32),
        (states.transpose(1, 0, 2, 3, 4).astype(jnp.float32),
         chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)   # [b, c, h, p, n]

    # decay from the chunk's start to q: exp(sum_{k<=q} dtA)
    decay_in = jnp.exp(dtAc.cumsum(axis=2))              # [b, c, q, h]
    y_inter = jnp.einsum("bcqhn,bcqh,bchpn->bcqhp", Ch, decay_in,
                         prev_states.astype(Ch.dtype))
    y = (y_intra + y_inter).reshape(b, l, h, p) + x * D[None, None, :, None]
    if return_state:
        return y.astype(x.dtype), final.astype(x.dtype)
    return y.astype(x.dtype)


def ssd_decode_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                    A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array):
    """Single-token recurrence. state: [b,h,p,n]; x: [b,h,p]; dt: [b,h];
    B,C: [b,g,n]. Returns (y [b,h,p], new_state)."""
    b, h, p = x.shape
    g = B.shape[1]
    Bh = jnp.repeat(B, h // g, axis=1)                   # [b,h,n]
    Ch = jnp.repeat(C, h // g, axis=1)
    decay = jnp.exp(dt * A[None, :])[..., None, None]    # [b,h,1,1]
    upd = (dt[..., None] * x)[..., None] * Bh[:, :, None, :]  # [b,h,p,n]
    new_state = state * decay + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch) + x * D[None, :, None]
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Mamba2 block (in_proj -> conv -> SSD -> gate -> out_proj)
# ---------------------------------------------------------------------------

def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, n_heads, conv_dim


def init_mamba2(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    s, d_in, nh, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 5)
    # in_proj emits [z (gate), x, B, C, dt]
    proj_out = 2 * d_in + 2 * s.n_groups * s.d_state + nh
    p = {
        "in_proj": init_dense(ks[0], cfg.d_model, proj_out, ("embed", "mlp"),
                              dtype),
        "conv_w": make_param(ks[1], (s.d_conv, conv_dim), (None, "mlp"),
                             dtype, scale=1.0 / s.d_conv),
        "conv_b": Param(jnp.zeros((conv_dim,), dtype), ("mlp",)),
        "A_log": Param(jnp.log(jnp.linspace(1.0, 16.0, nh,
                                            dtype=jnp.float32)), ("mlp",)),
        "D": Param(jnp.ones((nh,), jnp.float32), ("mlp",)),
        "dt_bias": Param(jnp.log(jnp.expm1(
            jnp.linspace(s.dt_min, s.dt_max, nh, dtype=jnp.float32))),
            ("mlp",)),
        "out_proj": init_dense(ks[2], d_in, cfg.d_model, ("mlp", "embed"),
                               dtype),
        "norm_scale": Param(jnp.ones((d_in,), jnp.float32), ("mlp",)),
    }
    return p


def _split_proj(cfg: ModelConfig, zxbcdt: jax.Array):
    s, d_in, nh, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    z, x, Bf, Cf, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + gn, 2 * d_in + 2 * gn], axis=-1)
    return z, x, Bf, Cf, dt


def _gated_norm(scale: jax.Array, y: jax.Array, z: jax.Array,
                eps: float) -> jax.Array:
    """Mamba2's RMSNorm(y * silu(z)) gate."""
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale).astype(y.dtype)


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                state: Optional[jax.Array] = None):
    """Depthwise causal conv1d. x: [B,L,C]; w: [K,C]. Returns y and the
    trailing K-1 inputs (next decode state)."""
    K = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(K))
    new_state = xp[:, -(K - 1):, :] if K > 1 else None
    return jax.nn.silu(y + b[None, None, :]), new_state


def mamba2_forward(params: Params, x: jax.Array, cfg: ModelConfig,
                   cache: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Mamba2 block. cache = (conv_state [B,K-1,conv_dim],
    ssd_state [B,H,P,N]) for decode (seq len 1); None for train/prefill.
    Returns (y, new_cache)."""
    from repro.kernels import ops
    s, d_in, nh, conv_dim = _dims(cfg)
    B_, L, _ = x.shape
    zxbcdt = dense(params["in_proj"], x)
    z, xr, Bf, Cf, dt = _split_proj(cfg, zxbcdt)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].value[None, None, :])
    A = -jnp.exp(params["A_log"].value)
    conv_in = jnp.concatenate([xr, Bf, Cf], axis=-1)

    if cache is None:
        conv_out, conv_tail = causal_conv(conv_in, params["conv_w"].value,
                                          params["conv_b"].value)
        xr, Bf, Cf = (conv_out[..., :d_in],
                      conv_out[..., d_in:d_in + s.n_groups * s.d_state],
                      conv_out[..., d_in + s.n_groups * s.d_state:])
        xh = xr.reshape(B_, L, nh, s.head_dim)
        Bh = Bf.reshape(B_, L, s.n_groups, s.d_state)
        Ch = Cf.reshape(B_, L, s.n_groups, s.d_state)
        pad = (-L) % s.chunk_size
        if pad:
            xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # the scan (forward, remat's recompute, backward) has a layer
        # scope of its own inside the block's ``ssd``
        with jax.named_scope("ssd_scan"):
            y, final_state = ops.ssd_chunked(
                xh, dt, A, Bh, Ch, params["D"].value, chunk=s.chunk_size,
                fallback=lambda x_, dt_, A_, B__, C__, D_, chunk:
                ssd_reference(x_, dt_, A_, B__, C__, D_, chunk=chunk,
                              return_state=True))
        y = y[:, :L].reshape(B_, L, d_in)
        new_cache = (conv_tail, final_state)
    else:
        conv_state, ssd_state = cache
        conv_out, conv_tail = causal_conv(conv_in, params["conv_w"].value,
                                          params["conv_b"].value, conv_state)
        xr, Bf, Cf = (conv_out[..., :d_in],
                      conv_out[..., d_in:d_in + s.n_groups * s.d_state],
                      conv_out[..., d_in + s.n_groups * s.d_state:])
        # L == 1 decode
        xh = xr[:, 0].reshape(B_, nh, s.head_dim)
        Bh = Bf[:, 0].reshape(B_, s.n_groups, s.d_state)
        Ch = Cf[:, 0].reshape(B_, s.n_groups, s.d_state)
        y1, new_state = ssd_decode_step(
            ssd_state.astype(jnp.float32), xh.astype(jnp.float32),
            dt[:, 0], A, Bh.astype(jnp.float32), Ch.astype(jnp.float32),
            params["D"].value)
        y = y1.reshape(B_, 1, d_in).astype(x.dtype)
        new_cache = (conv_tail, new_state.astype(ssd_state.dtype))

    y = _gated_norm(params["norm_scale"].value, y, z, cfg.norm_eps)
    return dense(params["out_proj"], y), new_cache
