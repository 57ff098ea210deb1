"""The chunk's busy time (union of its leaf operations) less the five
scoped layers: scan bookkeeping, ``rollout`` saves and loads, and
anything unnamed, in ms per update.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("unscoped_ms.marl")
