"""Share of its roofline that the Pallas SSD backward reaches: the least
time its calls need (the larger of FLOPs over the bf16 peak and bytes over
HBM bandwidth) over the summed device time of every kernel event whose
instruction is named ``ssd_scan_bwd*`` (the chunk-start states and the
gradients kernel together).

The least work is counted, not what the kernels do: per batch row and
chunk, once per B/C group C·Bᵀ and the dC and dB products within the
chunk (6Q²N), and per head dy·xᵀ and the scores' transpose times dy
(4Q²P) and the state's readout, its carry, and dx and dB through it
(8QPN); x, dy, B, C and dt read once and dx, dB, dC and ddt written once.
Recomputation is left out, so the reading cannot pass 100% for a correct
count of time. The shapes of a backward come from its gradients call, one
per backward, whose blocks put positions on lanes: results dx
[b, h, p, l], the per-position rows f32 [b, h, 3, l], dB and dC
[b, g, n, l]; the chunk length from the configuration. Where no call matches, as in a program whose backward is
not these kernels, the harness leaves the metric out.
"""
import re

from bench.roofline.peaks import peak_for
from bench.trace.reduce import result_shapes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_tokens_per_s", \
    "device_trace"
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}
_NAME = re.compile(r"^%?([\w.\-]+) = ")


def kernel_name(text):
    m = _NAME.match(text)
    return m.group(1).rsplit(".", 1)[0] if m else ""


def is_backward(text):
    return kernel_name(text).startswith("ssd_scan_bwd")


def call_shape(text):
    """(b, l, h, p, g, n, itemsize) of a gradients call, else None."""
    if kernel_name(text) != "ssd_scan_bwd_grads":
        return None
    res = result_shapes(text)
    if len(res) != 4 or res[0][0] not in _BYTES:
        return None
    (dt, dx), (_, rows), (_, db) = res[0], res[1], res[2]
    if len(dx) != 4 or len(db) != 4:
        return None
    b, h, p, l = dx
    if rows != (b, h, 3, l) or db[0] != b or db[3] != l:
        return None
    return b, l, h, p, db[1], db[2], _BYTES[dt]


def ssd_scan_bwd_cost(b, l, h, p, g, n, chunk, itemsize=2):
    """(FLOPs, bytes) one chunked SSD scan's backward needs at least."""
    q = chunk
    flops = b * (l // q) * (6.0 * q * q * n * g
                            + h * (4.0 * q * q * p + 8.0 * q * p * n))
    nbytes = itemsize * (3 * b * l * h * p + 4 * b * l * g * n) \
        + 4 * 2 * b * l * h
    return flops, float(nbytes)


def read(ctx):
    peak = peak_for(ctx.device_kind)
    chunk = ctx.cell.config["model"]["ssm"]["chunk_size"]
    need = spent = 0.0
    for op in ctx.trace.kernel_events(lambda o: is_backward(o.text)):
        spent += (op.end - op.start) / 1e9
        shape = call_shape(op.text)
        if shape:
            b, l, h, p, g, n, nb = shape
            flops, nbytes = ssd_scan_bwd_cost(b, l, h, p, g, n, chunk, nb)
            need += max(flops / peak.flops_bf16,
                        nbytes / peak.hbm_bytes_per_s)
    return 100.0 * need / spent if need else None
