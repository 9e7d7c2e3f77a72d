"""Device self time per step of the program's ``embed`` and ``head``
scopes (the token embedding; the final norm, the unembedding and the
cross-entropy), both directions (``bench.trace.scopes``)."""
from bench.trace import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "embed + head", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    return scopes.read(ctx, [(layer, d) for layer in ("embed", "head")
                             for d in ("fwd", "bwd")])
