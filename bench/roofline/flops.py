"""Operations and bytes from shapes: model FLOPs per token and the cost
of one call of each kernel.

Model FLOPs count what the forward and backward passes require, not what
the program computes: no recompute (remat), and causal attention counts
its unmasked half. ``model`` is the ``model`` block of a configuration
file under ``bench/configs``.
"""
from __future__ import annotations

from typing import Mapping, Tuple


def _ssm_dims(m: Mapping) -> Tuple[int, int, int, int, int, int]:
    s = m["ssm"]
    d_in = s["expand"] * m["d_model"]
    heads = d_in // s["head_dim"]
    conv_dim = d_in + 2 * s["n_groups"] * s["d_state"]
    return d_in, heads, s["head_dim"], s["n_groups"], s["d_state"], conv_dim


def param_count(m: Mapping) -> int:
    """All parameters, the tied embedding counted once."""
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    total = V * d + d                                   # embedding, final norm
    if not m["tie_embeddings"]:
        total += V * d
    if m["family"] == "dense":
        hd = m["head_dim"]
        attn = 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
        mlp = 3 * d * m["d_ff"]
        return total + L * (attn + mlp + 2 * d)
    if m["family"] == "ssm":
        d_in, h, _, g, n, conv_dim = _ssm_dims(m)
        K = m["ssm"]["d_conv"]
        in_proj = d * (2 * d_in + 2 * g * n + h)
        layer = (in_proj + K * conv_dim + conv_dim + 3 * h + d_in * d
                 + d_in + d)
        return total + L * layer
    raise ValueError(f"unknown family {m['family']!r}")


def sequence_flops_per_token(m: Mapping, seq: int) -> float:
    """Forward + backward FLOPs per token of the sequence-mixing part that
    the 6·N count leaves out: causal attention, or the chunked SSD."""
    L = m["n_layers"]
    if m["family"] == "dense":
        d_attn = m["n_heads"] * m["head_dim"]
        # QKᵀ and PV: 4·S·d_attn forward, ×3 with backward, halved by
        # the causal mask
        return 6.0 * L * seq * d_attn
    if m["family"] == "ssm":
        _, h, p, g, n, _ = _ssm_dims(m)
        Q = m["ssm"]["chunk_size"]
        # per token: C·Bᵀ per group (2·Q·N), and per head the intra-chunk
        # product (2·Q·P), the chunk state (2·P·N) and its readout (2·P·N)
        fwd = 2.0 * Q * n * g + h * (2.0 * Q * p + 4.0 * p * n)
        return 3.0 * L * fwd
    raise ValueError(f"unknown family {m['family']!r}")


def train_flops_per_token(m: Mapping, seq: int) -> float:
    return 6.0 * param_count(m) + sequence_flops_per_token(m, seq)


def flash_fwd_cost(B: int, Sq: int, Skv: int, Hq: int, Hkv: int, hd: int,
                   causal: bool, itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one flash-attention forward call needs: QKᵀ and PV
    over the attendable pairs; Q, K, V read and O written once."""
    pairs = Sq * (Sq + 1) / 2 if (causal and Sq == Skv) else Sq * Skv
    flops = 4.0 * B * Hq * pairs * hd
    nbytes = itemsize * hd * (2 * B * Sq * Hq + 2 * B * Skv * Hkv)
    nbytes += 4 * (Sq + Skv)                            # int32 positions
    return flops, float(nbytes)


def ssd_scan_fwd_cost(b: int, l: int, h: int, p: int, g: int, n: int,
                      chunk: int, itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one chunked SSD scan call needs: per chunk C·Bᵀ per
    group, and per head the intra-chunk product, the chunk state and its
    readout; x, B, C, dt read and y, the final state written once."""
    n_chunks = l // chunk
    flops = n_chunks * b * (2.0 * chunk * chunk * n * g
                            + h * (2.0 * chunk * chunk * p
                                   + 4.0 * chunk * p * n))
    nbytes = (itemsize * (2 * b * l * h * p + 2 * b * l * g * n
                          + b * h * p * n)
              + 4 * (b * l * h + 2 * h))
    return flops, float(nbytes)
