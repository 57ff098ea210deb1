"""Share of the bf16 peak that the held experts' grouped products reach:
the operations of the routed assignments the step counted (three
products a SwiGLU, forward and backward) over the device time of the
``experts`` layer. See bench/lmscopes.py."""
from bench import lmscopes


def read(ctx):
    return lmscopes.metrics(ctx).get("experts_roofline.lm")
