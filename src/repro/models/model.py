"""Model builder: ModelConfig -> init / train loss / prefill / decode.

The layer stack is organised into *segments*; each segment is a
``lax.scan`` over stacked per-layer parameters, so the compiled HLO stays
O(#segment-kinds), not O(#layers) — essential for the 512-device dry-run.

Segment kinds:
  attn_mlp    — pre-norm GQA attention + dense MLP (dense archs, whisper enc)
  lg_pair     — (local-window, global) attention pair (gemma2)
  mla_mlp     — MLA attention + dense MLP (deepseek dense prefix)
  mla_moe     — MLA attention + MoE (deepseek)
  attn_moe    — GQA attention + MoE with shared expert (llama4)
  ssm         — Mamba2 block (mamba2, zamba2 backbone)
  zamba_group — inner scan of `inner` ssm blocks + one *weight-shared*
                attention/MLP block (zamba2)
  dec_attn    — decoder block with cross-attention (whisper decoder)

Caches (decode) are pytrees matching the segment structure; attention
caches are ring buffers (see ``models.attention``), SSM caches are
(conv_state, ssd_state).
"""
from __future__ import annotations

import dataclasses
import math
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ATTN_LOCAL, ModelConfig, SSM
from repro.dist.sharding import BATCH, maybe_constrain, stream_gather
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.attention import AttnSpec
from repro.models.layers import (Param, Params, StreamDim, dense, init_dense,
                                 init_embedding, init_mlp, init_rmsnorm,
                                 is_param, make_param, mlp, paxes, pvalues,
                                 rmsnorm, softcap, unembed, with_values)

MASK_ID = -1                 # label value that is excluded from the loss
EMPTY_POS = 2 ** 30          # ring-cache "empty slot" position: +huge so the
                             # causal test (kv_pos <= q_pos) masks it out


@dataclass(frozen=True)
class SegmentSpec:
    kind: str
    n: int                    # scan length
    causal: bool = True
    window: int = 0           # sliding window (0 = global)
    inner: int = 0            # zamba_group: ssm layers per group


# ---------------------------------------------------------------------------
# Segment layout per architecture
# ---------------------------------------------------------------------------

def build_segments(cfg: ModelConfig) -> List[SegmentSpec]:
    if cfg.family == "ssm":
        return [SegmentSpec("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        k = cfg.shared_attn_every or cfg.n_layers
        groups, rem = divmod(cfg.n_layers, k)
        segs = []
        if groups:
            segs.append(SegmentSpec("zamba_group", groups, inner=k,
                                    window=cfg.attn_window))
        if rem:
            segs.append(SegmentSpec("ssm", rem))
        return segs
    if cfg.mla is not None:
        nd = cfg.moe.first_dense_layers if cfg.moe else cfg.n_layers
        segs = []
        if nd:
            segs.append(SegmentSpec("mla_mlp", nd))
        if cfg.n_layers - nd:
            segs.append(SegmentSpec("mla_moe", cfg.n_layers - nd))
        return segs
    if cfg.moe is not None:
        return [SegmentSpec("attn_moe", cfg.n_layers)]
    if cfg.local_global_pattern:
        assert cfg.n_layers % 2 == 0
        return [SegmentSpec("lg_pair", cfg.n_layers // 2,
                            window=cfg.attn_window)]
    if cfg.is_encoder_decoder:
        return [SegmentSpec("dec_attn", cfg.n_layers)]
    return [SegmentSpec("attn_mlp", cfg.n_layers)]


def tp_live_axes(cfg: ModelConfig, m: int) -> FrozenSet[str]:
    """Logical axes the manual tp step may keep *local* (partitioned).

    This is the semantic gate on top of the resolver's per-leaf
    divisibility rules: a logical name is "live" only when every layer
    that consumes leaves tagged with it handles a LocalDim marker.

      * heads/kv_heads couple for GQA: ``attend`` derives the group size
        from the shapes and q heads are laid out kv-major, so per-rank
        slices only align when both are cut by the same factor. MLA has
        no kv projection, so only n_heads gates it.
      * "mlp" is excluded whenever the stack contains ssm blocks: mamba2
        tags its packed in/out projections "mlp" with mixed per-channel
        semantics ([z, x, B, C, dt] share one dim) that no slice honours.
      * "expert" needs E % m == 0 for the expert-local dispatch; the
        router is excluded separately (its *last* dim is "expert" but
        routing needs full logits — see the step's plan builder).
      * "vocab"/"embed" never partition: the CE/logits path and the
        residual stream consume full arrays.
      * encoder-decoder stacks are excluded entirely: the cross-KV
        precompute reads segment weights outside the marker-aware paths.
    """
    if m <= 1 or cfg.is_encoder_decoder:
        return frozenset()
    kinds = {s.kind for s in build_segments(cfg)}
    live = set()
    if not (kinds & {"ssm", "zamba_group"}):
        live.add("mlp")
    if cfg.mla is not None:
        if cfg.n_heads % m == 0:
            live.add("heads")
    elif cfg.n_heads % m == 0 and cfg.n_kv_heads % m == 0:
        live.update(("heads", "kv_heads"))
    if cfg.moe is not None and cfg.moe.n_experts % m == 0:
        live.add("expert")
    return frozenset(live)


# ---------------------------------------------------------------------------
# Per-kind block init (single layer; stacking is done by the caller)
# ---------------------------------------------------------------------------

def _dt(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def init_block(key, cfg: ModelConfig, kind: str) -> Params:
    d, dt = cfg.d_model, _dt(cfg)
    ks = jax.random.split(key, 8)
    if kind in ("attn_mlp", "enc_attn"):
        return {"ln1": init_rmsnorm(d), "attn": A.init_gqa(ks[0], cfg, dt),
                "ln2": init_rmsnorm(d),
                "mlp": init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_activation, dt)}
    if kind == "lg_pair":
        return {"local": init_block(ks[0], cfg, "attn_mlp"),
                "global": init_block(ks[1], cfg, "attn_mlp")}
    if kind == "mla_mlp":
        return {"ln1": init_rmsnorm(d), "attn": A.init_mla(ks[0], cfg, dt),
                "ln2": init_rmsnorm(d),
                "mlp": init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_activation, dt)}
    if kind == "mla_moe":
        return {"ln1": init_rmsnorm(d), "attn": A.init_mla(ks[0], cfg, dt),
                "ln2": init_rmsnorm(d), "moe": M.init_moe(ks[1], cfg, dt)}
    if kind == "attn_moe":
        return {"ln1": init_rmsnorm(d), "attn": A.init_gqa(ks[0], cfg, dt),
                "ln2": init_rmsnorm(d), "moe": M.init_moe(ks[1], cfg, dt)}
    if kind == "ssm":
        return {"ln": init_rmsnorm(d), "mamba": S.init_mamba2(ks[0], cfg, dt)}
    if kind == "dec_attn":
        return {"ln1": init_rmsnorm(d), "attn": A.init_gqa(ks[0], cfg, dt),
                "ln2": init_rmsnorm(d), "xattn": A.init_gqa(ks[1], cfg, dt),
                "ln3": init_rmsnorm(d),
                "mlp": init_mlp(ks[2], d, cfg.d_ff, cfg.mlp_activation, dt)}
    raise ValueError(kind)


def _prepend_layers_axis(tree):
    from repro.models.layers import is_param
    return jax.tree.map(lambda p: Param(p.value, ("layers",) + p.axes),
                        tree, is_leaf=is_param)


def init_stacked(key, cfg: ModelConfig, kind: str, n: int) -> Params:
    """Stack n block inits with a leading 'layers' axis on every leaf."""
    keys = jax.random.split(key, n)
    stacked = jax.vmap(lambda k: init_block(k, cfg, kind))(keys)
    return _prepend_layers_axis(stacked)


def init_segment(key, cfg: ModelConfig, seg: SegmentSpec) -> Params:
    if seg.kind == "zamba_group":
        k1, k2 = jax.random.split(key)
        # inner ssm stacks: [groups, inner, ...]
        inner = jax.vmap(lambda k: init_stacked(k, cfg, "ssm", seg.inner))(
            jax.random.split(k1, seg.n))
        return {"inner": _prepend_layers_axis(inner),
                "shared": init_block(k2, cfg, "attn_mlp")}   # ONE copy
    return init_stacked(key, cfg, seg.kind, seg.n)


def init_model(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    segs = build_segments(cfg)
    params: Dict[str, Any] = {
        "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, _dt(cfg)),
        "final_norm": init_rmsnorm(cfg.d_model),
        "segments": [init_segment(k, cfg, s)
                     for k, s in zip(jax.random.split(ks[1], len(segs)), segs)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(ks[2], cfg.d_model, cfg.vocab_size,
                                       ("embed", "vocab"), _dt(cfg))
    if cfg.is_encoder_decoder:
        params["encoder"] = {
            "segments": [init_stacked(ks[3], cfg, "enc_attn",
                                      cfg.n_encoder_layers)],
            "final_norm": init_rmsnorm(cfg.d_model),
        }
    if cfg.mtp_depth:
        params["mtp"] = {
            "proj": init_dense(ks[4], 2 * cfg.d_model, cfg.d_model,
                               ("embed", "embed"), _dt(cfg)),
            "norm_h": init_rmsnorm(cfg.d_model),
            "norm_e": init_rmsnorm(cfg.d_model),
            "block": init_block(ks[5], cfg, "mla_mlp" if cfg.mla else
                                "attn_mlp"),
        }
    return params


# ---------------------------------------------------------------------------
# Per-kind block apply
# ---------------------------------------------------------------------------

def _attn_spec(cfg: ModelConfig, causal=True, window=0) -> AttnSpec:
    return AttnSpec(causal=causal, window=window,
                    logit_softcap=cfg.attn_logit_softcap,
                    scale=cfg.attn_scale_override)


def apply_block(params: Params, x, cfg: ModelConfig, kind: str, *,
                positions, cache=None, cache_pos=None, window=0,
                causal=True, enc_kv=None):
    """Returns (x, new_cache, aux_loss)."""
    # pin batch->data at every block boundary: without this GSPMD may
    # replicate batch inside attention and all-reduce score tensors
    # (llama4 train_4k baseline: 33 TB/chip of score all-reduces)
    x = maybe_constrain(x, BATCH, None, None)
    aux = jnp.zeros((), jnp.float32)
    eps = cfg.norm_eps
    if kind == "lg_pair":
        x, c0, a0 = apply_block(params["local"], x, cfg, "attn_mlp",
                                positions=positions,
                                cache=None if cache is None else cache[0],
                                cache_pos=cache_pos, window=window)
        x, c1, a1 = apply_block(params["global"], x, cfg, "attn_mlp",
                                positions=positions,
                                cache=None if cache is None else cache[1],
                                cache_pos=cache_pos, window=0)
        return x, (c0, c1), a0 + a1

    # each sublayer (pre-norm, body, residual add) under its layer scope
    if kind in ("attn_mlp", "enc_attn"):
        spec = _attn_spec(cfg, causal=causal, window=window)
        with jax.named_scope("attention"):
            h, new_cache = A.gqa_forward(params["attn"],
                                         rmsnorm(params["ln1"], x, eps), cfg,
                                         spec, positions, cache, cache_pos)
            x = x + h
        with jax.named_scope("mlp"):
            x = x + mlp(params["mlp"], rmsnorm(params["ln2"], x, eps),
                        cfg.mlp_activation)
        return x, new_cache, aux

    if kind in ("mla_mlp", "mla_moe"):
        spec = _attn_spec(cfg, causal=causal, window=window)
        with jax.named_scope("attention"):
            h, new_cache = A.mla_forward(params["attn"],
                                         rmsnorm(params["ln1"], x, eps), cfg,
                                         spec, positions, cache, cache_pos)
            x = x + h
        if kind == "mla_mlp":
            with jax.named_scope("mlp"):
                x = x + mlp(params["mlp"], rmsnorm(params["ln2"], x, eps),
                            cfg.mlp_activation)
        else:
            with jax.named_scope("moe"):
                out = M.moe_forward(params["moe"],
                                    rmsnorm(params["ln2"], x, eps), cfg)
                x, aux = x + out.y, out.aux_loss
        return x, new_cache, aux

    if kind == "attn_moe":
        spec = _attn_spec(cfg, causal=causal, window=window)
        with jax.named_scope("attention"):
            h, new_cache = A.gqa_forward(params["attn"],
                                         rmsnorm(params["ln1"], x, eps), cfg,
                                         spec, positions, cache, cache_pos)
            x = x + h
        with jax.named_scope("moe"):
            out = M.moe_forward(params["moe"], rmsnorm(params["ln2"], x, eps),
                                cfg)
            x = x + out.y
        return x, new_cache, out.aux_loss

    if kind == "ssm":
        with jax.named_scope("ssd"):
            h, new_cache = S.mamba2_forward(params["mamba"],
                                            rmsnorm(params["ln"], x, eps),
                                            cfg, cache)
            x = x + h
        return x, new_cache, aux

    if kind == "dec_attn":
        spec = _attn_spec(cfg, causal=True)
        with jax.named_scope("attention"):
            h, self_cache = A.gqa_forward(params["attn"],
                                          rmsnorm(params["ln1"], x, eps),
                                          cfg, spec, positions, cache,
                                          cache_pos)
            x = x + h
            h, _ = A.gqa_forward(params["xattn"],
                                 rmsnorm(params["ln2"], x, eps), cfg,
                                 AttnSpec(causal=False), positions,
                                 kv_override=enc_kv)
            x = x + h
        with jax.named_scope("mlp"):
            x = x + mlp(params["mlp"], rmsnorm(params["ln3"], x, eps),
                        cfg.mlp_activation)
        return x, self_cache, aux

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Streaming parameter gathers (overlap train step)
# ---------------------------------------------------------------------------
# The overlap step leaves ZeRO-sharded segment leaves sharded, marks the
# sharded dims with StreamDim in the axes tuples, and installs this
# context while the loss traces; the per-layer scan bodies then gather
# each leaf *inside* the layer's compute (repro.dist.sharding.
# stream_gather, whose custom backward is the fused reduce-scatter).
# Trace-time thread-local, same pattern as sharding.manual_mode.

class _StreamCtx(threading.local):
    def __init__(self):
        self.cfg = None


_STREAM = _StreamCtx()


@contextmanager
def stream_context(sizes: Tuple[Tuple[str, int], ...],
                   batch_axes: Tuple[str, ...], mode: str):
    """sizes: mesh {axis: size} as sorted pairs; mode: grad wire format."""
    prev = _STREAM.cfg
    _STREAM.cfg = (tuple(sizes), tuple(batch_axes), mode)
    try:
        yield
    finally:
        _STREAM.cfg = prev


def _stream_in(p: Param) -> Param:
    """Gather one scanned leaf's StreamDim dims; identity when unmarked."""
    if not any(isinstance(e, StreamDim) for e in p.axes):
        return p
    if _STREAM.cfg is None:
        raise RuntimeError("StreamDim-marked params outside a "
                           "stream_context (overlap train step)")
    sizes, batch_axes, mode = _STREAM.cfg
    nd = p.value.ndim
    # scan slices values per-layer but axes keep the leading "layers"
    # entry; align entries to the value's trailing dims
    entries = tuple(e.entry if isinstance(e, StreamDim) else None
                    for e in p.axes[-nd:]) if nd else ()
    v = stream_gather(entries, sizes, batch_axes, mode, p.value)
    axes = tuple(e.logical if isinstance(e, StreamDim) else e
                 for e in p.axes)
    return Param(v, axes)


def stream_in_params(tree):
    return jax.tree.map(_stream_in, tree, is_leaf=is_param)


# ---------------------------------------------------------------------------
# Segment apply (scan over stacked layers)
# ---------------------------------------------------------------------------

def _remat_wrap(f, policy: str):
    if policy == "none":
        return f
    if policy == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(f)          # "full": save nothing


def apply_segment(params: Params, x, cfg: ModelConfig, seg: SegmentSpec, *,
                  positions, cache=None, cache_pos=None, enc_kv=None,
                  keep_cache=False, remat="none"):
    """Scan a segment. Returns (x, new_cache, aux_sum)."""
    if seg.kind == "zamba_group":
        shared = params["shared"]

        def group_body(h, xs):
            p_inner, c = xs
            ic = None if cache is None else c[0]
            sc = None if cache is None else c[1]
            h, new_ic, aux = apply_segment(
                p_inner, h, cfg, SegmentSpec("ssm", seg.inner),
                positions=positions, cache=ic, cache_pos=cache_pos,
                keep_cache=keep_cache, remat="none")
            h, new_sc, aux2 = apply_block(
                shared, h, cfg, "attn_mlp", positions=positions,
                cache=sc, cache_pos=cache_pos, window=seg.window)
            if not keep_cache and cache is None:
                new_ic = new_sc = None
            return h, ((new_ic, new_sc), aux + aux2)

        group_body = _remat_wrap(group_body, remat)
        x, (new_cache, auxs) = jax.lax.scan(group_body, x,
                                            (params["inner"], cache))
        return x, new_cache, auxs.sum()

    def body(h, xs):
        p, c = xs
        p = stream_in_params(p)
        h, new_c, aux = apply_block(p, h, cfg, seg.kind, positions=positions,
                                    cache=c, cache_pos=cache_pos,
                                    window=seg.window, causal=seg.causal,
                                    enc_kv=None)
        if not keep_cache and cache is None:
            new_c = None
        return h, (new_c, aux)

    if seg.kind == "dec_attn":
        def body(h, xs):                                  # noqa: F811
            p, c, ekv = xs
            p = stream_in_params(p)
            h, new_c, aux = apply_block(p, h, cfg, seg.kind,
                                        positions=positions, cache=c,
                                        cache_pos=cache_pos, enc_kv=ekv)
            if not keep_cache and cache is None:
                new_c = None
            return h, (new_c, aux)
        body = _remat_wrap(body, remat)
        x, (new_cache, auxs) = jax.lax.scan(body, x, (params, cache, enc_kv))
        return x, new_cache, auxs.sum()

    body = _remat_wrap(body, remat)
    x, (new_cache, auxs) = jax.lax.scan(body, x, (params, cache))
    return x, new_cache, auxs.sum()


# ---------------------------------------------------------------------------
# Full model: hidden states
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens):
    with jax.named_scope("embed"):
        h = params["embed"]["table"].value[tokens]
        if cfg.scale_embeddings:
            h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
        return h


def encoder_forward(params, cfg: ModelConfig, frames, remat="none"):
    """Whisper encoder over precomputed (stub) frame embeddings."""
    h = frames.astype(_dt(cfg))
    pos = jnp.arange(frames.shape[1])
    h, _, _ = apply_segment(params["encoder"]["segments"][0], h, cfg,
                            SegmentSpec("enc_attn", cfg.n_encoder_layers,
                                        causal=False),
                            positions=pos, remat=remat)
    return rmsnorm(params["encoder"]["final_norm"], h, cfg.norm_eps)


def hidden_forward(params, cfg: ModelConfig, h, *, positions, caches=None,
                   cache_pos=None, enc_kv=None, keep_cache=False,
                   remat="none"):
    """Run all segments. h: [B,S,D]. Returns (h, caches, aux)."""
    segs = build_segments(cfg)
    new_caches, aux = [], jnp.zeros((), jnp.float32)
    for i, seg in enumerate(segs):
        c = None if caches is None else caches[i]
        h, nc, a = apply_segment(params["segments"][i], h, cfg, seg,
                                 positions=positions, cache=c,
                                 cache_pos=cache_pos, enc_kv=enc_kv,
                                 keep_cache=keep_cache, remat=remat)
        new_caches.append(nc)
        aux = aux + a
    with jax.named_scope("head"):
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return h, new_caches, aux


def logits_fn(params, cfg: ModelConfig, h):
    with jax.named_scope("head"):
        if cfg.tie_embeddings or "lm_head" not in params:
            logits = unembed(params["embed"], h)
        else:
            logits = jnp.einsum("...d,dv->...v", h,
                                params["lm_head"]["kernel"].value,
                                preferred_element_type=jnp.float32)
        if cfg.final_logit_softcap:
            logits = softcap(logits, cfg.final_logit_softcap)
        logits = maybe_constrain(logits, *([None] * (logits.ndim - 1)),
                                 "model")
        return logits.astype(jnp.bfloat16)   # sharded [.., vocab]; CE fp32


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, impl: str = "gather"):
    """logits [..., V] (bf16 ok), labels int (MASK_ID = ignore).
    Returns (sum_ce_fp32, n_tokens).

    impl="gather": take_along_axis — simple, but under a vocab-sharded
      logits layout GSPMD lowers the gather to an all-gather of the full
      logits (the baseline's dominant collective).
    impl="onehot": label log-prob extracted with an iota==label mask and a
      reduction over the (sharded) vocab axis — lowers to an elementwise
      select + per-shard reduce + tiny psum; no logits all-gather.
    """
    with jax.named_scope("head"):
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        lab = jnp.maximum(labels, 0)
        if impl == "onehot":
            iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
            ll = jnp.sum(jnp.where(iota == lab[..., None], lf, 0.0), axis=-1)
        else:
            ll = jnp.take_along_axis(lf, lab[..., None], axis=-1)[..., 0]
        mask = (labels != MASK_ID)
        ce = (lse - ll) * mask
        return ce.sum(), mask.sum()


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            remat: str = "full", ce_impl: str = "gather"):
    """Training loss. batch: tokens [B,S]; optional patches/frames; optional
    labels (default: next-token)."""
    tokens = batch["tokens"]
    B, S_text = tokens.shape
    h = embed_tokens(params, cfg, tokens)
    enc_kv = None

    if cfg.frontend == "vision_patch_stub":
        patches = batch["patches"].astype(h.dtype)       # [B, n_front, D]
        h = jnp.concatenate([patches, h], axis=1)
    S = h.shape[1]
    positions = jnp.arange(S)

    if cfg.is_encoder_decoder:
        enc_out = encoder_forward(params, cfg, batch["frames"], remat=remat)
        enc_kv = _stacked_cross_kv(params, cfg, enc_out)

    h, _, aux = hidden_forward(params, cfg, h, positions=positions,
                               remat=remat)

    with jax.named_scope("head"):
        if "labels" in batch:
            labels = batch["labels"]
        else:
            labels = jnp.concatenate(
                [tokens[:, 1:], jnp.full((B, 1), MASK_ID, tokens.dtype)],
                axis=1)
        if cfg.frontend == "vision_patch_stub":
            n_f = batch["patches"].shape[1]
            labels = jnp.concatenate(
                [jnp.full((B, n_f), MASK_ID, labels.dtype), labels], axis=1)

        logits = logits_fn(params, cfg, h)
        ce_sum, n_tok = cross_entropy(logits, labels, impl=ce_impl)
        loss = ce_sum / jnp.maximum(n_tok, 1)
    metrics = {"ce": loss, "aux": aux, "tokens": n_tok}

    if cfg.mtp_depth and not cfg.is_encoder_decoder:
        mtp = params["mtp"]
        h_in = rmsnorm(mtp["norm_h"], h[:, :-1], cfg.norm_eps)
        e_in = rmsnorm(mtp["norm_e"],
                       embed_tokens(params, cfg, tokens[:, 1:]), cfg.norm_eps)
        hm = dense(mtp["proj"], jnp.concatenate([h_in, e_in], axis=-1))
        kind = "mla_mlp" if cfg.mla else "attn_mlp"
        hm, _, _ = apply_block(mtp["block"], hm, cfg, kind,
                               positions=positions[:-1])
        hm = rmsnorm(params["final_norm"], hm, cfg.norm_eps)
        mtp_logits = logits_fn(params, cfg, hm)
        mtp_labels = labels[:, 1:]   # position t predicts token t+2
        mtp_sum, mtp_n = cross_entropy(mtp_logits, mtp_labels,
                                       impl=ce_impl)
        mtp_ce = mtp_sum / jnp.maximum(mtp_n, 1)
        loss = loss + cfg.mtp_loss_weight * mtp_ce
        metrics["mtp_ce"] = mtp_ce

    loss = loss + aux
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill / decode
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch, *, remat: str = "none"):
    """Full forward keeping caches. Returns (last-position logits, caches,
    enc_kv-or-None)."""
    tokens = batch["tokens"]
    h = embed_tokens(params, cfg, tokens)
    if cfg.frontend == "vision_patch_stub":
        h = jnp.concatenate([batch["patches"].astype(h.dtype), h], axis=1)
    positions = jnp.arange(h.shape[1])
    enc_kv = None
    if cfg.is_encoder_decoder:
        enc_out = encoder_forward(params, cfg, batch["frames"], remat=remat)
        enc_kv = _stacked_cross_kv(params, cfg, enc_out)
    h, caches, _ = hidden_forward(params, cfg, h, positions=positions,
                                  enc_kv=enc_kv, keep_cache=True, remat=remat)
    logits = logits_fn(params, cfg, h[:, -1:])
    return logits[:, 0], caches, enc_kv


def _stacked_cross_kv(params, cfg: ModelConfig, enc_out):
    """Per-decoder-layer cross K/V, stacked on a leading layer axis."""
    seg_vals = pvalues(params["segments"][0])
    B, T, _ = enc_out.shape
    hd = cfg.get_head_dim()

    def layer_kv(blk):
        k = jnp.einsum("btd,df->btf", enc_out, blk["xattn"]["wk"]["kernel"])
        v = jnp.einsum("btd,df->btf", enc_out, blk["xattn"]["wv"]["kernel"])
        return (k.reshape(B, T, cfg.n_kv_heads, hd),
                v.reshape(B, T, cfg.n_kv_heads, hd))

    return jax.vmap(layer_kv)(seg_vals)


def decode_step(params, cfg: ModelConfig, caches, token, pos, *,
                enc_kv=None):
    """One decode step. token [B,1]; pos scalar int (absolute position).
    Returns (logits [B,V], new caches)."""
    h = embed_tokens(params, cfg, token)
    positions = jnp.full((1,), pos, jnp.int32)
    h, new_caches, _ = hidden_forward(params, cfg, h, positions=positions,
                                      caches=caches, cache_pos=pos,
                                      enc_kv=enc_kv, keep_cache=True)
    return logits_fn(params, cfg, h)[:, 0], new_caches


# ---------------------------------------------------------------------------
# Decode-cache construction
# ---------------------------------------------------------------------------

def _zeros_leaf(shape, dtype, role):
    if role == "pos":
        return jnp.full(shape, EMPTY_POS, jnp.int32)
    return jnp.zeros(shape, dtype)


def _attn_cache(cfg: ModelConfig, B: int, cap: int, n, dtype, mk) -> Tuple:
    hd = cfg.get_head_dim()
    lead = () if n is None else (n,)
    shp = lead + (B, cap, cfg.n_kv_heads, hd)
    return (mk(shp, dtype, "kv"), mk(shp, dtype, "kv"),
            mk(lead + (cap,), jnp.int32, "pos"))


def _mla_cache(cfg: ModelConfig, B: int, cap: int, n, dtype, mk):
    m = cfg.mla
    lead = () if n is None else (n,)
    return (mk(lead + (B, cap, m.kv_lora_rank), dtype, "lat"),
            mk(lead + (B, cap, m.qk_rope_head_dim), dtype, "rope"),
            mk(lead + (cap,), jnp.int32, "pos"))


def _ssm_cache(cfg: ModelConfig, B: int, n, dtype, mk,
               lead_extra: Tuple = ()):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    lead = lead_extra + (() if n is None else (n,))
    return (mk(lead + (B, s.d_conv - 1, conv_dim), dtype, "conv"),
            mk(lead + (B, nh, s.head_dim, s.d_state), jnp.float32, "ssd"))


def build_decode_caches(cfg: ModelConfig, B: int, seq_cap: int,
                        dtype=jnp.bfloat16, mk=_zeros_leaf) -> List:
    """Cache pytree matching hidden_forward; ``mk(shape, dtype, role)``
    constructs leaves (zeros by default; the dry-run passes a
    ShapeDtypeStruct+sharding constructor)."""
    caches = []
    for seg in build_segments(cfg):
        if seg.kind == "ssm":
            caches.append(_ssm_cache(cfg, B, seg.n, dtype, mk))
        elif seg.kind in ("attn_mlp", "dec_attn"):
            cap = min(seq_cap, seg.window) if seg.window else seq_cap
            caches.append(_attn_cache(cfg, B, cap, seg.n, dtype, mk))
        elif seg.kind == "attn_moe":
            caches.append(_attn_cache(cfg, B, seq_cap, seg.n, dtype, mk))
        elif seg.kind in ("mla_mlp", "mla_moe"):
            caches.append(_mla_cache(cfg, B, seq_cap, seg.n, dtype, mk))
        elif seg.kind == "lg_pair":
            local_cap = min(seq_cap, seg.window or seq_cap)
            caches.append((_attn_cache(cfg, B, local_cap, seg.n, dtype, mk),
                           _attn_cache(cfg, B, seq_cap, seg.n, dtype, mk)))
        elif seg.kind == "zamba_group":
            # inner ssm caches: [groups, inner, ...]
            inner_s = cfg.ssm
            inner = jax.tree.map(
                lambda x: x, _ssm_cache(cfg, B, seg.inner, dtype, mk,
                                        lead_extra=(seg.n,)))
            cap = min(seq_cap, seg.window) if seg.window else seq_cap
            shared = _attn_cache(cfg, B, cap, seg.n, dtype, mk)
            caches.append((inner, shared))
        else:
            raise ValueError(seg.kind)
    return caches


def init_decode_caches(cfg: ModelConfig, B: int, seq_cap: int,
                       dtype=jnp.bfloat16) -> List:
    """Zeroed caches matching hidden_forward's cache pytree."""
    return build_decode_caches(cfg, B, seq_cap, dtype)
