"""Device time of the latent attention (``mla`` scope, the flash kernels
inside it), forward and backward, in ms per step. See bench/lmscopes.py."""
from bench import lmscopes


def read(ctx):
    return lmscopes.metrics(ctx).get("mla_ms.lm")
