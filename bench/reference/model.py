"""Plain float32 references of the benchmark's language models.

Straight from the published descriptions, in ``jax.numpy``, with no
kernels, caches or sharding, and nothing imported from the program:

* ``dense``: pre-norm decoder; GQA attention (query head i reads KV head
  i // (Hq/Hkv)), rotary embeddings on the two halves of each head,
  causal softmax; SwiGLU MLP ``down(silu(gate x) * up x)``; RMSNorm;
  the unembedding is the tied embedding table.
* ``ssm`` (Mamba2): pre-norm blocks of in_proj -> [z, x, B, C, dt];
  depthwise causal conv + SiLU over [x, B, C]; dt = softplus(dt + bias);
  the SSD output written in its quadratic ("attention") form
  y_t = sum_{s<=t} C_t·B_s · exp(sum_{s<k<=t} dt_k A) · dt_s x_s + D x_t,
  gated RMSNorm(y * silu(z)); out_proj.

Every matrix product runs at ``Precision.HIGHEST``. ``lowp="fp8"`` is
the control: each product's operands are rounded to fp8 with one scale
per tensor (e4m3 forward, e5m2 for the gradients they receive), the
nearest precision below the configuration's bf16.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
f32 = jnp.float32


def _round_to(x, dtype):
    fmax = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / fmax, 1.0)
    return (x / s).astype(dtype).astype(f32) * s


@jax.custom_vjp
def fp8(x):
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(spec: str, a, b, lowp: Optional[str]):
    if lowp == "fp8":
        a, b = fp8(a), fp8(b)
    elif lowp is not None:
        raise ValueError(f"unknown lowp {lowp!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [B,S,H,hd]; rotate the (first half, second half) pairs."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd)
    ang = jnp.arange(S, dtype=f32)[:, None] * inv           # [S, hd/2]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, lowp):
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, Hq // Hkv, hd)
    s = mm("bqkgh,btkh->bkgqt", qg, k, lowp) / jnp.sqrt(f32(hd))
    mask = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = mm("bkgqt,btkh->bqkgh", p, v, lowp)
    return o.reshape(B, S, Hq * hd)


def dense_layer(p: Dict, h, m: Mapping, lowp):
    B, S, _ = h.shape
    hd = m["head_dim"]
    x = rmsnorm(h, p["ln1/scale"], m["norm_eps"])
    q = mm("bsd,df->bsf", x, p["attn/wq/kernel"], lowp).reshape(B, S, -1, hd)
    k = mm("bsd,df->bsf", x, p["attn/wk/kernel"], lowp).reshape(B, S, -1, hd)
    v = mm("bsd,df->bsf", x, p["attn/wv/kernel"], lowp).reshape(B, S, -1, hd)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    o = causal_attention(q, k, v, lowp)
    h = h + mm("bsf,fd->bsd", o, p["attn/wo/kernel"], lowp)
    x = rmsnorm(h, p["ln2/scale"], m["norm_eps"])
    g = mm("bsd,df->bsf", x, p["mlp/gate/kernel"], lowp)
    u = mm("bsd,df->bsf", x, p["mlp/up/kernel"], lowp)
    return h + mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["mlp/down/kernel"],
                  lowp)


def ssd_quadratic(x, dt, A, Bm, Cm, D, lowp):
    """x [b,l,h,p]; dt [b,l,h]; A, D [h]; Bm, Cm [b,l,g,n] -> [b,l,h,p]."""
    b, l, h, _ = x.shape
    g = Bm.shape[2]
    cs = jnp.cumsum(dt * A, axis=1)                          # [b,l,h]
    seg = cs[:, :, None, :] - cs[:, None, :, :]              # [b,t,s,h]
    causal = jnp.tril(jnp.ones((l, l), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = mm("btgn,bsgn->btsg", Cm, Bm, lowp)
    cb = jnp.repeat(cb, h // g, axis=-1)                     # [b,t,s,h]
    w = cb * decay * dt[:, None, :, :]
    return mm("btsh,bshp->bthp", w, x, lowp) + x * D[:, None]


def ssm_layer(p: Dict, h, m: Mapping, lowp):
    s = m["ssm"]
    B, L, _ = h.shape
    d_in = s["expand"] * m["d_model"]
    nh, P = d_in // s["head_dim"], s["head_dim"]
    G, N, K = s["n_groups"], s["d_state"], s["d_conv"]
    x = rmsnorm(h, p["ln/scale"], m["norm_eps"])
    zx = mm("bld,de->ble", x, p["mamba/in_proj/kernel"], lowp)
    z, xbc, dt = jnp.split(zx, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    dt = jax.nn.softplus(dt + p["mamba/dt_bias"])
    A = -jnp.exp(p["mamba/A_log"])
    w, bias = p["mamba/conv_w"], p["mamba/conv_b"]           # [K,C], [C]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + L] * w[i] for i in range(K))
    xbc = jax.nn.silu(conv + bias)
    xs, Bm, Cm = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    y = ssd_quadratic(xs.reshape(B, L, nh, P), dt, A, Bm.reshape(B, L, G, N),
                      Cm.reshape(B, L, G, N), p["mamba/D"], lowp)
    y = y.reshape(B, L, d_in) * jax.nn.silu(z)
    y = rmsnorm(y, p["mamba/norm_scale"], m["norm_eps"])
    return h + mm("ble,ed->bld", y, p["mamba/out_proj/kernel"], lowp)


LAYERS = {"dense": dense_layer, "ssm": ssm_layer}


def loss_sum(params: Dict, m: Mapping, tokens, lowp: Optional[str] = None):
    """Sum over rows and positions of the next-token cross-entropy
    (the last position has no label). params: {path: f32 array}."""
    seg = "segments/0/"
    stacked = {k[len(seg):]: v for k, v in params.items()
               if k.startswith(seg)}
    table = params["embed/table"]
    h = table[tokens]
    layer = jax.checkpoint(partial(LAYERS[m["family"]], m=m, lowp=lowp))

    def body(h, p):
        return layer(p, h), None

    h, _ = jax.lax.scan(body, h, stacked)
    h = rmsnorm(h, params["final_norm/scale"], m["norm_eps"])
    head = (table if m["tie_embeddings"] else params["lm_head/kernel"].T)
    logits = mm("bsd,vd->bsv", h[:, :-1], head, lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)
