"""Device self time per step of the SSD chunked scan's backward: the ops
under the program's ``ssd_scan`` scope inside a transpose and outside
remat's recompute (``bench.trace.scopes``). Nothing to read where the
program has no ``ssd_scan`` scope."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "ssd scan", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    if "ssd_scan" not in (scopes.layer_scopes() or ()):
        return None
    return scopes.read(ctx, [("ssd_scan", "bwd")])
