"""Share of the traced window in which device 0 ran no operation: one
minus the union of its operation intervals over the window."""
from bench import trace


def read(ctx):
    return trace.idle_pct(ctx)
