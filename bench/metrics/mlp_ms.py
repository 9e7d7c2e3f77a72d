"""Device self time per step of the program's ``mlp`` scope, forward,
recompute and backward (``bench.trace.scopes``)."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "mlp", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    return scopes.read(ctx, [("mlp", "fwd"), ("mlp", "bwd")])
