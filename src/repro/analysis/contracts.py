"""Runtime trace/compile contracts.

The repo's amortization claims are *count* claims: ``make_plan`` traces
once per FLGW layer per refresh, zero times per decode step; a jitted
step compiles once per shape and never again mid-run. Before this module
every test enforcing a count claim hand-rolled the same monkeypatch::

    calls = {"n": 0}
    real = grouped.make_plan
    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)
    monkeypatch.setattr(grouped, "make_plan", counting)

and nothing at all watched for silent recompiles in the serving/async
hot loops. This module is the shared replacement:

* :func:`trace_counter` — the counting monkeypatch as a context manager
  (count, reset, call-through semantics identical to the old idiom);
* :func:`assert_max_traces` — the common assertion form in one line;
* :func:`no_retrace` — a compile monitor built on ``jax.log_compiles``:
  every XLA compile inside the context is recorded, and leaving the
  context raises :class:`RetraceError` if any function compiled more
  than once (a mid-run recompile — shape instability, a cache-defeating
  weak-ref loss, or an accidentally-traced Python bool). This is the
  engine behind the opt-in ``debug_contracts=True`` hooks on
  ``ServeSession``/``Engine`` and ``marl.async_train``.
"""
from __future__ import annotations

import contextlib
import logging
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

__all__ = [
    "ContractViolation", "RetraceError", "TraceCounter", "CompileMonitor",
    "trace_counter", "assert_max_traces", "no_retrace",
]


class ContractViolation(AssertionError):
    """A runtime trace/compile contract did not hold."""


class RetraceError(ContractViolation):
    """A jitted function compiled more than once inside ``no_retrace``."""


# ---------------------------------------------------------------------------
# trace counting (the make_plan idiom, shared)
# ---------------------------------------------------------------------------

@dataclass
class TraceCounter:
    """Live handle yielded by :func:`trace_counter`.

    ``count`` increments on every call of the wrapped attribute —
    including calls under ``jax.eval_shape``/``jit`` tracing, which is
    the point: the number of *traces* is the amortization contract.
    """
    module: object = None
    attr: str = ""
    count: int = 0
    calls: List[Tuple[tuple, dict]] = field(default_factory=list)

    def reset(self) -> None:
        self.count = 0
        self.calls.clear()

    def __int__(self) -> int:
        return self.count


@contextlib.contextmanager
def trace_counter(module, attr: str, *, record_args: bool = False):
    """Count calls to ``module.attr`` while delegating to the original.

    The one replacement for the per-file ``counting`` +
    ``monkeypatch.setattr(module, attr, counting)`` copies::

        with trace_counter(grouped, "make_plan") as calls:
            jax.eval_shape(step, state, batch)
        assert calls.count == n_layers

    The original attribute is restored on exit even if the body raises.
    ``record_args=True`` additionally keeps ``(args, kwargs)`` per call
    on ``calls.calls`` for tests that assert on arguments.
    """
    real = getattr(module, attr)
    counter = TraceCounter(module=module, attr=attr)

    def counting(*a, **kw):
        counter.count += 1
        if record_args:
            counter.calls.append((a, kw))
        return real(*a, **kw)

    counting.__name__ = getattr(real, "__name__", attr)
    counting.__wrapped__ = real
    setattr(module, attr, counting)
    try:
        yield counter
    finally:
        setattr(module, attr, real)


@contextlib.contextmanager
def assert_max_traces(module, attr: str, n: int, *,
                      exactly: bool = False):
    """Context form of the count assertion: at most (or exactly) ``n``
    traces of ``module.attr`` inside the block, else
    :class:`ContractViolation`.
    """
    with trace_counter(module, attr) as counter:
        yield counter
    if exactly and counter.count != n:
        raise ContractViolation(
            f"{getattr(module, '__name__', module)}.{attr} traced "
            f"{counter.count} time(s); contract requires exactly {n}")
    if counter.count > n:
        raise ContractViolation(
            f"{getattr(module, '__name__', module)}.{attr} traced "
            f"{counter.count} time(s); contract allows at most {n}")


# ---------------------------------------------------------------------------
# recompile guard (jax.log_compiles)
# ---------------------------------------------------------------------------

# jax logs one WARNING-level record per XLA compile when jax_log_compiles
# is on: "Compiling <name> with global shapes and types [...]" — emitted
# by the pxla/dispatch internals. The logger names are version-dependent
# internals, so we hook every plausible one; the message prefix is the
# stable part.
_COMPILE_LOGGERS = (
    "jax._src.interpreters.pxla",
    "jax._src.dispatch",
    "jax._src.pjit",
)
# jax 0.9 logs the name wrapped as ``jit(<name>)``; the bare name is kept.
_COMPILE_RE = re.compile(r"^Compiling (?:jit\(([^\s()]+)\)|([^\s]+))")

# Eager jnp/lax/random ops executed outside any user jit compile under
# the *library function's* name — sometimes the public one ("less",
# "select_n", "take_along_axis"), sometimes a private implementation
# helper ("_where" for jnp.where, "_threefry_split" for
# jax.random.split, "_broadcast_arrays") — and the log record is
# indistinguishable from a user jit's. They legitimately compile once
# per operand shape (or per static arg, e.g. the split count):
# host-side bookkeeping around a hot loop — masking a ragged flush,
# stacking a variable-width window, splitting a key — is not the
# retrace class this guard exists for. So compiles whose name matches a
# callable defined in any loaded ``jax.*`` module are exempt from the
# offender check (still recorded on the monitor). The set is rebuilt at
# each context exit so modules imported mid-block are covered. The flip
# side: a user jit that shadows a jax callable name ("where", "scan",
# "update") escapes the guard — name it something else.

def _library_op_names() -> frozenset:
    import sys
    names = set()
    for modname, mod in list(sys.modules.items()):
        if mod is None or not (modname == "jax"
                               or modname.startswith("jax.")):
            continue
        for attr in dir(mod):
            try:
                if callable(getattr(mod, attr, None)):
                    names.add(attr)
            except Exception:      # a broken lazy attribute must not kill us
                pass
    return frozenset(names)


@dataclass
class CompileEvent:
    name: str          # jitted function name as jax logged it
    message: str       # full log record (includes the abstract shapes)


class CompileMonitor:
    """Collects the compile events seen inside a ``no_retrace`` block."""

    def __init__(self) -> None:
        self.events: List[CompileEvent] = []

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for ev in self.events:
            out[ev.name] = out.get(ev.name, 0) + 1
        return out

    def shapes(self, name: str) -> List[str]:
        return [ev.message for ev in self.events if ev.name == name]


class _CompileHandler(logging.Handler):
    def __init__(self, monitor: CompileMonitor):
        super().__init__(level=logging.DEBUG)
        self.monitor = monitor

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:          # a malformed record must not kill the run
            return
        m = _COMPILE_RE.match(msg)
        if m:
            self.monitor.events.append(
                CompileEvent(m.group(1) or m.group(2), msg))


@contextlib.contextmanager
def no_retrace(*, max_compiles: int = 1, allow: Tuple[str, ...] = (),
               label: str = "", monitor: Optional[CompileMonitor] = None):
    """Fail if any jitted function compiles more than ``max_compiles``
    times inside the block.

    The contract behind the serving/async hot loops: after the first
    step of a run compiles each jitted function once per shape, *no*
    further compiles may happen mid-run — a second compile of the same
    function means the loop is feeding shape-unstable inputs (or
    re-tracing through a lost jit cache), exactly the silent stall class
    "Characterizing Speed Performance of MARL" measures. Function names
    in ``allow`` are exempt (e.g. a deliberately polymorphic helper), as
    are eager jnp/lax library ops (see ``_library_op_names``), which
    compile once per shape by design.

    Usage::

        with no_retrace(label="Engine.run") as mon:
            for _ in range(steps):
                tok, cache = session.decode(cache, tok, pos)
        # raises RetraceError if any function compiled twice

    First compiles are allowed (``max_compiles=1``); a warmed-up caller
    can pass ``max_compiles=0`` to forbid any compile at all. Nesting is
    safe; the monitor only sees compiles issued while the block is
    active (on any thread — jax's compile log is process-global, which
    is what makes this catch the threaded async pipeline too).
    """
    mon = monitor if monitor is not None else CompileMonitor()
    handler = _CompileHandler(mon)
    loggers = []
    for name in _COMPILE_LOGGERS:
        lg = logging.getLogger(name)
        # the records arrive at WARNING; make sure they are not filtered
        # out before our handler sees them, and restore the level after
        prev_level = lg.level
        if not lg.isEnabledFor(logging.WARNING):
            lg.setLevel(logging.WARNING)
        lg.addHandler(handler)
        loggers.append((lg, prev_level))
    try:
        with jax.log_compiles(True):
            yield mon
    finally:
        for lg, prev_level in loggers:
            lg.removeHandler(handler)
            lg.setLevel(prev_level)
    library = _library_op_names()
    offenders = {name: n for name, n in mon.counts().items()
                 if n > max_compiles and name not in allow
                 and name not in library}
    if offenders:
        where = f" in {label}" if label else ""
        lines = []
        for name, n in sorted(offenders.items()):
            lines.append(f"  {name}: compiled {n}x "
                         f"(allowed {max_compiles})")
            for msg in mon.shapes(name):
                lines.append(f"    - {msg}")
        raise RetraceError(
            f"recompile contract violated{where}: a jitted step "
            f"recompiled mid-run\n" + "\n".join(lines))
