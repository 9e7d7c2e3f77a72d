"""Share of its roofline that the Pallas flash-attention backward
reaches: the least time its calls need (the larger of FLOPs over the bf16
peak and bytes over HBM bandwidth) over the summed device time of every
kernel event whose instruction is named ``flash_bwd*`` (the log-sum-exp,
dQ and dK/dV kernels together).

The least work is counted, not what the kernels do: five products
over the attendable pairs (QKᵀ, dO·Vᵀ, Pᵀ·dO, dSᵀ·Q, dS·K), 2.5 times
the forward's; q, k, v, o and dO read once and dq, dk, dv written once. So
the reading cannot pass 100% for a correct count of time. The shapes of
a backward come from its dQ call, one per backward: result dq
[B·Hkv, G, Sq, hd], and k [B·Hkv, Skv, hd] the first operand of three
dimensions in its dtype. Square calls are causal (training
self-attention). Where no call matches, as in a program whose backward
is not these kernels, the harness leaves the metric out.
"""
import re

from bench.roofline.peaks import peak_for
from bench.trace.reduce import operand_shapes, result_shapes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_tokens_per_s", \
    "device_trace"
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}
_NAME = re.compile(r"^%?([\w.\-]+) = ")


def kernel_name(text):
    m = _NAME.match(text)
    return m.group(1).rsplit(".", 1)[0] if m else ""


def is_backward(text):
    return kernel_name(text).startswith("flash_bwd")


def call_shape(text):
    """(Hq rows, Hkv rows, Sq, Skv, hd, itemsize) of a dQ call, else None."""
    if kernel_name(text) != "flash_bwd_dq":
        return None
    res = result_shapes(text)
    if len(res) != 1 or len(res[0][1]) != 4 or res[0][0] not in _BYTES:
        return None
    dt, (rk, g, sq, hd) = res[0]
    kv = [s for d, s in operand_shapes(text)
          if d == dt and len(s) == 3 and s[0] == rk and s[2] == hd]
    if not kv:
        return None
    return rk * g, rk, sq, kv[0][1], hd, _BYTES[dt]


def flash_bwd_cost(B, Sq, Skv, Hq, Hkv, hd, causal, itemsize=2):
    """(FLOPs, bytes) one flash-attention backward needs at least: five
    products over the attendable pairs; q, o, dO, k, v read and dq, dk,
    dv written once."""
    pairs = Sq * (Sq + 1) / 2 if (causal and Sq == Skv) else Sq * Skv
    flops = 10.0 * B * Hq * pairs * hd
    nbytes = itemsize * hd * (4 * B * Sq * Hq + 4 * B * Skv * Hkv)
    return flops, float(nbytes)


def read(ctx):
    peak = peak_for(ctx.device_kind)
    need = spent = 0.0
    for op in ctx.trace.kernel_events(lambda o: is_backward(o.text)):
        spent += (op.end - op.start) / 1e9
        shape = call_shape(op.text)
        if shape:
            rq, rk, sq, skv, hd, nb = shape
            flops, nbytes = flash_bwd_cost(1, sq, skv, rq, rk, hd,
                                           causal=(sq == skv), itemsize=nb)
            need += max(flops / peak.flops_bf16,
                        nbytes / peak.hbm_bytes_per_s)
    return 100.0 * need / spent if need else None
