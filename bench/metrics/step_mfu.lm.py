"""Operations the traced steps need (bench/lmwork.py: forward and
backward, causal attention by its half, the routed experts by the
assignments the step counted) over the traced window, the chips and the
bf16 peak. See bench/lmscopes.py."""
from bench import lmscopes


def read(ctx):
    return lmscopes.metrics(ctx).get("step_mfu.lm")
