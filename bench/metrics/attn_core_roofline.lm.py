"""Share of the bf16 peak that the flash attention kernels reach: the
causal attention core's operations (by its half, forward and backward,
no recompute) over the device time of ``flash_fwd``, ``flash_dq`` and
``flash_dkdv``. See bench/lmscopes.py."""
from bench import lmscopes


def read(ctx):
    return lmscopes.metrics(ctx).get("attn_core_roofline.lm")
