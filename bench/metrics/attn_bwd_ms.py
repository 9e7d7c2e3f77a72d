"""Device self time per step of the attention layer's backward: the ops
under the program's ``attention`` scope inside a transpose and outside
remat's recompute (``bench.trace.scopes``)."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "attention", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    return scopes.read(ctx, [("attention", "bwd")])
