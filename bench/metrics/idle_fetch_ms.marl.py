"""Device-idle time in the traced window, on the aligned clock, while the
host is inside ``np.asarray(jax.Array)``: the metrics fetch, in ms per
dispatch.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("idle_fetch_ms.marl")
