# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# NOTE: this module must stay importable WITHOUT jax — the static
# kernel auditor (repro.analysis.kernel_audit) and the per-package
# audit.py KernelSpec modules run in the jax-free CI analysis job, and
# they import repro.kernels.tiling through this package. jax imports
# live inside the functions that need them.
import contextlib as _contextlib

# Shared reference-impl mode for every Pallas kernel in this package:
# under plain jit, GSPMD cannot partition a pallas custom call — it
# replicates the kernel computation on every chip. On real TPUs kernels
# run under shard_map on local blocks; for CPU dry-runs the launcher
# lowers the mathematically identical jnp references instead, which GSPMD
# shards like any einsum. One switch covers flgw_matmul AND plan_encode so
# a lowering never mixes modes.
_REF_MODE: list = []


@_contextlib.contextmanager
def use_reference_impl():
    _REF_MODE.append(True)
    try:
        yield
    finally:
        _REF_MODE.pop()


def reference_impl_active() -> bool:
    return bool(_REF_MODE)


def tpu_compiler_params(**kwargs):
    """Mosaic compiler params (``pltpu.CompilerParams``) for a kernel."""
    from jax.experimental.pallas import tpu as _pltpu
    return _pltpu.CompilerParams(**kwargs)


def default_interpret() -> bool:
    """Whether a Pallas kernel runs in interpret mode when its caller does
    not say: on every backend but the TPU (the CPU test suite), since
    Mosaic compiles for the TPU only."""
    import jax
    return jax.default_backend() != "tpu"
