"""Median host time of the jit call of the step in the timed window,
from the call until it returns (the device runs on after)."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "dispatch", "train_tokens_per_s", \
    "host_clock"


def read(ctx):
    return 1e3 * statistics.median(ctx.dispatch_s)
