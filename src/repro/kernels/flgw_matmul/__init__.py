# Lazy re-exports (PEP 562): importing the package must not pull in jax,
# so the jax-free audit module (audit.py / repro.analysis.kernel_audit)
# can load its KernelSpecs in the no-jax CI analysis job.
_EXPORTS = {
    "compact_weights": "ops", "grouped_matmul": "ops", "reference": "ops",
    "grouped_bmm": "flgw_matmul",
}
__all__ = list(_EXPORTS)


def __getattr__(name):
    import importlib
    mod = _EXPORTS.get(name)
    if mod is not None:
        return getattr(
            importlib.import_module(f"{__name__}.{mod}"), name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
