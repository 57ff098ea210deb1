"""Device time of sampling (``sample`` scope: key split, categorical,
bernoulli, log-probabilities; forward and backward), in ms per update.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("sample_ms.marl")
