"""Device self time per step of the program's ``optimizer`` scope:
gradient compression, clipping, the schedule and the update
(``bench.trace.scopes``)."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "optimizer", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    return scopes.read(ctx, [("optimizer", "fwd"), ("optimizer", "bwd")])
