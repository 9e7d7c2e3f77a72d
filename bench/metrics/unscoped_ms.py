"""Device self time per step that no layer scope of the program claims:
the layer scan's own slicing and loop control, and any op the compiled
text does not name. With the five layer metrics it sums to the traced
window's device self time per step, so it is their coverage check
(``bench.trace.scopes``)."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "device", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    return scopes.read(ctx, [None])
