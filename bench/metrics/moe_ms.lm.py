"""Device time of the MoE layers (``moe_route``, ``experts``,
``shared_experts``), forward and backward, in ms per step.
See bench/lmscopes.py."""
from bench import lmscopes


def read(ctx):
    return lmscopes.metrics(ctx).get("moe_ms.lm")
