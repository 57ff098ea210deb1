"""Device time of the environment (``env`` scope: reset, observe, step,
the done freeze, success) inside the traced chunk, in ms per update.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("env_ms.marl")
