"""Device time of the A2C loss (``a2c``, forward and backward) and the
RMSprop update (``rmsprop``), in ms per update.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("loss_optim_ms.marl")
