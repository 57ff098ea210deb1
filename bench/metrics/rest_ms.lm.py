"""The step's busy time (union of its leaf operations) less ``mla_ms.lm``
and ``moe_ms.lm``: the dense layer's MLP, the head and loss, AdamW, the
norms, the embedding and anything unnamed, in ms per step.
See bench/lmscopes.py."""
from bench import lmscopes


def read(ctx):
    return lmscopes.metrics(ctx).get("rest_ms.lm")
