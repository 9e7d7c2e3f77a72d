"""Share of the traced window in which no op ran on the device, mean
over the cell's chips."""
UNIT, LAYER, MOVES, SOURCE = "%", "device", "train_tokens_per_s", \
    "device_trace"


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
