"""Core layer library: parameters with logical sharding axes + primitives.

Parameters are plain ``Param(value, axes)`` leaves in nested dicts. ``axes``
names the *logical* mesh axes of each dimension ("embed", "heads", "mlp",
"expert", "vocab", "layers", ...); ``repro.dist.sharding`` maps logical
axes to physical mesh axes per parallelism strategy. This keeps the model
code entirely mesh-agnostic — the same definitions run on 1 CPU device and
on a 512-chip multi-pod mesh.
"""
from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class Param:
    """An array leaf annotated with *logical* sharding axes.

    Registered as a pytree node whose ``axes`` are static aux-data, so
    ``vmap``/``scan``/``jit`` traverse the value transparently while the
    annotation rides along (this is what lets us ``lax.scan`` over stacked
    per-layer parameter trees)."""
    __slots__ = ("value", "axes")

    def __init__(self, value, axes: Tuple[Optional[str], ...]):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"Param(shape={shape}, axes={self.axes})"


Params = Any  # nested dict of Param


@dataclass(frozen=True)
class LocalDim:
    """Axes-entry marker: this dimension holds a 1/``size`` *local* slice.

    The manual (shard_map) tensor-parallel step rewrites the axes tuples
    of the parameters it keeps sharded over the model axis, replacing the
    logical name with ``LocalDim(logical, axis, size)``. Layer code
    branches on ``isinstance(entry, LocalDim)`` to insert the Megatron
    collectives (row-parallel ``psum``, the ``tp_f`` identity/psum pair)
    — everything else sees plain logical names and runs unchanged.

    NB: inside ``lax.scan`` bodies the *values* are layer-sliced while
    the static axes tuples keep their leading "layers" entry, so checks
    must index axes from the right (``axes[-1]``, ``axes[-2]``, ...).
    """
    logical: str
    axis: str
    size: int


def local_dim(entry) -> Optional["LocalDim"]:
    return entry if isinstance(entry, LocalDim) else None


@dataclass(frozen=True)
class StreamDim:
    """Axes-entry marker: this dim is ZeRO-sharded and *streamed*.

    The overlap train step leaves such leaves sharded and the per-layer
    scan body all-gathers them just before use (``stream_gather`` in
    ``repro.dist.sharding``), so parameter gathers and gradient
    reduce-scatters interleave with each layer's compute instead of
    serializing around the loss. ``entry`` is the PartitionSpec entry of
    the dim (mesh-axis name or tuple of names).
    """
    logical: Optional[str]
    entry: Any


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_f(axis_name: str, x: jax.Array) -> jax.Array:
    """Megatron's ``f`` operator: identity forward, all-reduce backward.

    Placed at the entry of each *partitioned* sub-path (MLP input,
    attention input, MoE dispatch) so the backward pass completes the
    partial input-cotangents each model rank produces. It must wrap only
    partitioned sub-paths: the transpose of ``psum`` is the identity, so
    a replicated sub-path sharing an ``f``-wrapped input would get its
    (already complete) cotangent multiplied by the ring size.
    """
    return x


def _tp_f_fwd(axis_name, x):
    return x, None


def _tp_f_bwd(axis_name, _, g):
    return (jax.lax.psum(g, axis_name),)


tp_f.defvjp(_tp_f_fwd, _tp_f_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_g(axis_name: str, x: jax.Array) -> jax.Array:
    """Megatron's ``g`` operator: all-reduce forward, identity backward.

    Closes a row-parallel product (partial per-rank sums -> full output).
    It must be this custom pair rather than a raw ``lax.psum``: under
    ``shard_map(check_vma=False)`` the transpose of ``psum`` is ``psum``
    again, which would multiply the (replicated) output cotangent by the
    ring size on the way back. The true adjoint of "sum the partials" is
    "hand each rank the output cotangent unchanged".
    """
    return jax.lax.psum(x, axis_name)


def _tp_g_fwd(axis_name, x):
    return jax.lax.psum(x, axis_name), None


def _tp_g_bwd(axis_name, _, g):
    return (g,)


tp_g.defvjp(_tp_g_fwd, _tp_g_bwd)


class _TpProbe(threading.local):
    def __init__(self):
        self.sink = None


_TP_PROBE = _TpProbe()


@contextmanager
def tp_probe_sink(records: list):
    """Record ``(tag, shape)`` of probed activations at trace time.

    ``tools/overlap_smoke.py`` uses this to prove the manual tp step
    really shards activations over the model axis: tracing the step with
    a sink installed captures the *local* hidden shapes seen inside the
    shard_map body.
    """
    prev = _TP_PROBE.sink
    _TP_PROBE.sink = records
    try:
        yield records
    finally:
        _TP_PROBE.sink = prev


def tp_probe(tag: str, x: jax.Array) -> jax.Array:
    if _TP_PROBE.sink is not None:
        _TP_PROBE.sink.append((tag, tuple(x.shape)))
    return x


def is_param(x) -> bool:
    return isinstance(x, Param)


def pvalues(tree):
    """Strip axes annotations -> pytree of raw arrays."""
    return jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)


def paxes(tree):
    """Pytree of logical-axis tuples, matching pvalues(tree)."""
    return jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)


def with_values(tree, values):
    """Re-attach raw arrays to an axes skeleton."""
    return jax.tree.map(lambda p, v: Param(v, p.axes), tree, values,
                        is_leaf=is_param)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def make_param(key, shape: Sequence[int], axes: Sequence[Optional[str]],
               dtype=jnp.bfloat16, scale: Optional[float] = None,
               init: str = "normal") -> Param:
    shape = tuple(shape)
    assert len(shape) == len(axes), (shape, axes)
    if init == "zeros":
        v = jnp.zeros(shape, dtype)
    elif init == "ones":
        v = jnp.ones(shape, dtype)
    else:
        if scale is None:  # fan-in scaling
            fan_in = shape[0] if len(shape) else 1
            scale = 1.0 / math.sqrt(max(fan_in, 1))
        v = (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    return Param(v, tuple(axes))


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def activation_fn(name: str):
    if name in ("silu", "geglu"):  # gating handled by the MLP structure
        return jax.nn.silu if name == "silu" else jax.nn.gelu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu":
        return jax.nn.relu
    if name == "sqrelu":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "tanh":
        return jnp.tanh
    if name == "sigmoid":
        return jax.nn.sigmoid
    raise ValueError(f"unknown activation {name!r}")


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma2-style logit soft-capping: cap * tanh(x / cap)."""
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, axis: str = "embed") -> Params:
    return {"scale": Param(jnp.ones((d,), jnp.float32), (None,))}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].value).astype(dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.bfloat16) -> Params:
    return {"table": make_param(key, (vocab, d), ("vocab", "embed"),
                                dtype=dtype, scale=0.02)}


def embed(params: Params, tokens: jax.Array) -> jax.Array:
    return params["table"].value[tokens]


def unembed(params: Params, x: jax.Array) -> jax.Array:
    t = params["table"].value
    return jnp.einsum("...d,vd->...v", x, t,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # [head_dim/2]


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, n, head_dim]; positions: broadcastable to [..., S]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    angles = angles[..., None, :]                              # head axis
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / MLP blocks
# ---------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, axes: Tuple[Optional[str], ...],
               dtype=jnp.bfloat16, bias: bool = False,
               bias_axis: Optional[str] = None) -> Params:
    p = {"kernel": make_param(key, (d_in, d_out), axes, dtype=dtype)}
    if bias:
        p["bias"] = Param(jnp.zeros((d_out,), dtype), (bias_axis,))
    return p


def dense(params: Params, x: jax.Array) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, params["kernel"].value)
    row = local_dim(params["kernel"].axes[-2])
    if row is not None:  # row-parallel: partial products, reduce before bias
        y = tp_g(row.axis, y)
    if "bias" in params:
        y = y + params["bias"].value
    return y


def init_mlp(key, d_model: int, d_ff: int, activation: str,
             dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    gated = activation in ("silu", "geglu")
    p = {"up": init_dense(ks[0], d_model, d_ff, ("embed", "mlp"), dtype),
         "down": init_dense(ks[1], d_ff, d_model, ("mlp", "embed"), dtype)}
    if gated:
        p["gate"] = init_dense(ks[2], d_model, d_ff, ("embed", "mlp"), dtype)
    return p


def mlp(params: Params, x: jax.Array, activation: str) -> jax.Array:
    act = activation_fn(activation)
    col = local_dim(params["up"]["kernel"].axes[-1])
    if col is not None:  # column-parallel entry: complete cotangents on bwd
        x = tp_f(col.axis, x)
    up = dense(params["up"], x)
    if "gate" in params:
        h = act(dense(params["gate"], x)) * up
    else:
        h = act(up)
    h = tp_probe("mlp_hidden", h)
    return dense(params["down"], h)


# ---------------------------------------------------------------------------
# Attention masks
# ---------------------------------------------------------------------------

NEG_INF = -2.3819763e38  # large negative for bf16-safe masking


def causal_mask(q_len: int, kv_len: int, q_offset=0) -> jax.Array:
    """[q_len, kv_len] boolean; True = attendable."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return kv_pos <= q_pos


def sliding_window_mask(q_len: int, kv_len: int, window: int,
                        q_offset=0) -> jax.Array:
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos <= q_pos) & (kv_pos > q_pos - window)
