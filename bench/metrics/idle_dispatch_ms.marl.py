"""Device-idle time in the traced window, on the aligned clock, while the
host is inside a ``PjitFunction(...)``: the ``start`` scalar's convert and
the chunk's launch, in ms per dispatch.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("idle_dispatch_ms.marl")
