"""Model FLOP utilization of the training step over the timed window:
tokens/s × model FLOPs per token ÷ (chips × the chip's bf16 peak).
Model FLOPs: ``bench.roofline.flops.train_flops_per_token`` (no
recompute)."""
from bench.roofline.flops import train_flops_per_token
from bench.roofline.peaks import peak_for

UNIT, LAYER, MOVES, SOURCE = "%", "train step", "train_tokens_per_s", \
    "host_clock"


def read(ctx):
    per_token = train_flops_per_token(ctx.cell.config["model"],
                                      ctx.cell.traffic["seq_len"])
    peak = peak_for(ctx.device_kind).flops_bf16 * ctx.chips
    return 100.0 * ctx.tokens_per_s * per_token / peak
