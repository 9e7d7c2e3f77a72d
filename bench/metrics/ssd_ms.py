"""Device self time per step of the Mamba2 block outside its scan: the
ops under the program's ``ssd`` scope that no inner ``ssd_scan`` claims
(pre-norm, ``in_proj``, the depthwise conv, dt and the gating, the gated
norm, ``out_proj``, the residual add), forward, recompute and backward
(``bench.trace.scopes``). Where the program has no ``ssd_scan`` scope,
``ssd`` would hold the scan too, so there is nothing to read."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "ssd", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    if "ssd_scan" not in (scopes.layer_scopes() or ()):
        return None
    return scopes.read(ctx, [("ssd", "fwd"), ("ssd", "bwd")])
