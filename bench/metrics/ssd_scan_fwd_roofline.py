"""Share of its roofline that the Pallas SSD chunked scan reaches: the
least time its calls need (the larger of FLOPs over the bf16 peak and
bytes over HBM bandwidth, from the shapes of each call and the chunk
length of the configuration) over the summed device time of its events.

The kernel reaches the trace as a ``tpu_custom_call`` with no name; it is
the one whose operands are x [b, h, l, p], dt as [b, h, l, 1] and
[b, h, 1, l], A [h], B and C [b, g, l, n], D [h].
"""
from bench.roofline.flops import ssd_scan_fwd_cost
from bench.roofline.peaks import peak_for
from bench.trace.reduce import operand_shapes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_tokens_per_s", \
    "device_trace"
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def call_shape(text):
    ops = operand_shapes(text)
    if len(ops) != 7 or [len(o[1]) for o in ops] != [4, 4, 4, 1, 4, 4, 1]:
        return None
    (dt, (b, h, l, p)), (_, (_, g, _, n)) = ops[0], ops[4]
    if ops[1][1] != (b, h, l, 1) or ops[2][1] != (b, h, 1, l):
        return None
    return b, l, h, p, g, n, _BYTES[dt]


def read(ctx):
    peak = peak_for(ctx.device_kind)
    chunk = ctx.cell.config["model"]["ssm"]["chunk_size"]
    need = spent = 0.0
    for op in ctx.trace.kernel_events(lambda o: call_shape(o.text)):
        b, l, h, p, g, n, nb = call_shape(op.text)
        flops, nbytes = ssd_scan_fwd_cost(b, l, h, p, g, n, chunk, nb)
        need += max(flops / peak.flops_bf16, nbytes / peak.hbm_bytes_per_s)
        spent += (op.end - op.start) / 1e9
    return 100.0 * need / spent if spent else None
