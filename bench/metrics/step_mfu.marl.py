"""Operations the traced updates need (bench.flops: every projection of
every agent at every env step, forward and backward) over the traced
window, the chips and the bf16 peak. IC3Net's float32 products run as
bfloat16 passes at the default precision, so the bf16 peak bounds them."""
from bench import trace


def read(ctx):
    return trace.mfu_pct(ctx)
