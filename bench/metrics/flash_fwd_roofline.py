"""Share of its roofline that the Pallas flash-attention forward reaches:
the least time its calls need (the larger of FLOPs over the bf16 peak and
bytes over HBM bandwidth, from the shapes of each call) over the summed
device time of its events.

The kernel reaches the trace as a ``tpu_custom_call`` with no name; it is
the one whose first three operands are q [B·Hq, Sq, hd], k and v
[B·Hkv, Skv, hd] and whose one result has q's shape. Operands after
those (today int32 positions [Sq, 1] and [1, Skv]) may change without
hiding the kernel. Square calls are causal (training self-attention).
Where no call matches, the harness leaves the metric out and says so on
stderr.
"""
from bench.roofline.flops import flash_fwd_cost
from bench.roofline.peaks import peak_for
from bench.trace.reduce import operand_shapes, result_shapes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_tokens_per_s", \
    "device_trace"
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def call_shape(text):
    ops, res = operand_shapes(text), result_shapes(text)
    if len(ops) < 3 or len(res) != 1 or res[0] != ops[0]:
        return None
    (dt, q), (kdt, k), v = ops[:3]
    if (dt not in _BYTES or kdt != dt or v != ops[1] or len(q) != 3
            or len(k) != 3 or q[2] != k[2] or q[0] % k[0]):
        return None
    return q[0], k[0], q[1], k[1], q[2], _BYTES[dt]


def read(ctx):
    peak = peak_for(ctx.device_kind)
    need = spent = 0.0
    for op in ctx.trace.kernel_events(lambda o: call_shape(o.text)):
        rq, rk, sq, skv, hd, nb = call_shape(op.text)
        flops, nbytes = flash_fwd_cost(1, sq, skv, rq, rk, hd,
                                       causal=(sq == skv), itemsize=nb)
        need += max(flops / peak.flops_bf16, nbytes / peak.hbm_bytes_per_s)
        spent += (op.end - op.start) / 1e9
    return 100.0 * need / spent if spent else None
