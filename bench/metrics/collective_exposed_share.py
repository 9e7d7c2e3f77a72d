"""Share of the traced window in which a collective runs on a chip and
no other op does, mean over chips. Nothing to read without collectives."""
UNIT, LAYER, MOVES, SOURCE = "%", "collectives", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    exposed = ctx.trace.collective_exposed_s()
    if exposed is None:
        return None
    return 100.0 * exposed / ctx.trace.window_s()
