"""Device self time per step of the attention layer's forward work: the
ops under the program's ``attention`` scope that are not its backward,
so the forward and remat's recompute, the flash kernel's calls included
(``bench.trace.scopes``)."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "attention", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    return scopes.read(ctx, [("attention", "fwd")])
