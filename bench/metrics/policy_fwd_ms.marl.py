"""Device time of the IC3Net forward step (``policy`` and its ``comm``,
``encoder``, ``lstm`` and ``heads`` scopes, outside ``transpose(``), in ms
per update.
See bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.metrics(ctx).get("policy_fwd_ms.marl")
