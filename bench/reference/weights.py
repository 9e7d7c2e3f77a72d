"""The benchmark's own weights, made from the seed.

The layout (one flat dict, keyed by the path of each leaf in the
program's parameter tree) is written down here from the configuration
alone, so the program and the reference start from the same numbers
without the reference taking anything the program made. The harness
checks the program's parameter tree against ``param_shapes``: any leaf
the program adds, drops, reshapes or stores in another dtype is an error.

Init: matrices N(0, 1/fan_in), the embedding N(0, 0.02), the conv taps
N(0, 1/d_conv), norm scales 1, biases 0; Mamba2's A = -[1..16] and dt
in [dt_min, dt_max] spaced evenly over the heads, D = 1.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> (init rule, stored in the configuration's param dtype?)
_RULES = {
    "table": ("embed", True),
    "kernel": ("fan_in", True),
    "conv_w": ("conv", True),
    "conv_b": ("zeros", True),
    "scale": ("ones", False),
    "norm_scale": ("ones", False),
    "A_log": ("a_log", False),
    "D": ("ones", False),
    "dt_bias": ("dt_bias", False),
}

Shapes = Dict[str, Tuple[int, ...]]


def param_shapes(m: Mapping) -> Shapes:
    """{path: shape} of every parameter, stacked layers first."""
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    out: Shapes = {"embed/table": (V, d), "final_norm/scale": (d,)}
    seg = "segments/0/"
    if m["family"] == "dense":
        hq, hkv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
        out.update({
            seg + "ln1/scale": (L, d), seg + "ln2/scale": (L, d),
            seg + "attn/wq/kernel": (L, d, hq),
            seg + "attn/wk/kernel": (L, d, hkv),
            seg + "attn/wv/kernel": (L, d, hkv),
            seg + "attn/wo/kernel": (L, hq, d),
            seg + "mlp/gate/kernel": (L, d, m["d_ff"]),
            seg + "mlp/up/kernel": (L, d, m["d_ff"]),
            seg + "mlp/down/kernel": (L, m["d_ff"], d),
        })
    elif m["family"] == "ssm":
        s = m["ssm"]
        d_in = s["expand"] * d
        h = d_in // s["head_dim"]
        gn = s["n_groups"] * s["d_state"]
        conv_dim = d_in + 2 * gn
        mb = seg + "mamba/"
        out.update({
            seg + "ln/scale": (L, d),
            mb + "in_proj/kernel": (L, d, 2 * d_in + 2 * gn + h),
            mb + "conv_w": (L, s["d_conv"], conv_dim),
            mb + "conv_b": (L, conv_dim),
            mb + "A_log": (L, h), mb + "D": (L, h), mb + "dt_bias": (L, h),
            mb + "out_proj/kernel": (L, d_in, d),
            mb + "norm_scale": (L, d_in),
        })
    else:
        raise ValueError(f"unknown family {m['family']!r}")
    if not m["tie_embeddings"]:
        out["lm_head/kernel"] = (d, V)
    return out


def _rule(path: str):
    return _RULES[path.rsplit("/", 1)[-1]]


def param_dtypes(m: Mapping) -> Dict[str, jnp.dtype]:
    """{path: dtype} the configuration stores each parameter in."""
    pdt = jnp.dtype(m["param_dtype"])
    return {p: (pdt if _rule(p)[1] else jnp.dtype(jnp.float32))
            for p in param_shapes(m)}


def _init(path: str, shape, key, m: Mapping) -> jax.Array:
    rule = _rule(path)[0]
    if rule == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if rule == "ones":
        return jnp.ones(shape, jnp.float32)
    if rule in ("a_log", "dt_bias"):
        s, h = m["ssm"], shape[-1]
        if rule == "a_log":
            v = jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32))
        else:
            dt = jnp.linspace(s["dt_min"], s["dt_max"], h, dtype=jnp.float32)
            v = jnp.log(jnp.expm1(dt))      # softplus(dt_bias) = dt
        return jnp.broadcast_to(v, shape)
    if rule == "embed":
        std = 0.02
    elif rule == "conv":
        std = 1.0 / m["ssm"]["d_conv"]
    else:                                   # fan_in: [..., d_in, d_out]
        std = 1.0 / math.sqrt(shape[-2])
    return jax.random.normal(key, shape, jnp.float32) * std


def make_params(m: Mapping, key: jax.Array) -> Dict[str, jax.Array]:
    """{path: array} in the configuration's stored dtypes. Jit it: every
    leaf is drawn on the device from ``key`` folded with its path."""
    dts = param_dtypes(m)
    return {p: _init(p, shp, jax.random.fold_in(key, zlib.crc32(p.encode())),
                     m).astype(dts[p])
            for p, shp in param_shapes(m).items()}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (more than 32 bits hold)."""
    word = np.random.SeedSequence([seed, 0]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)
