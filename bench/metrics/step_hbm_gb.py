"""Device memory of the compiled train step, per chip, in GB: its
arguments, the outputs not aliased to them, and its temporaries, as the
compiler's ``memory_analysis()`` of the step the window runs counts
them. ``peak_bytes_in_use`` of the TPU runtime leaves the temporaries
out, so it cannot show a change to activation memory; this does."""
UNIT, LAYER, MOVES, SOURCE = "GB", "memory", "train_tokens_per_s", \
    "program_counter"


def read(ctx):
    return None if ctx.step_bytes is None else ctx.step_bytes / 1e9
