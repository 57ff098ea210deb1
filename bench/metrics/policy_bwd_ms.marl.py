"""Device time of the IC3Net backward (``policy`` and its children, under
``transpose(``), in ms per update.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("policy_bwd_ms.marl")
